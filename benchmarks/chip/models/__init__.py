"""One module per architecture; ``arch.py`` says what each provides."""
