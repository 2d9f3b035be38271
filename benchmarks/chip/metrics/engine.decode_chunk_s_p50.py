"""Engine: median seconds of one decode chunk dispatch, fenced on the
device (the program's ``engine.decode_chunk_s`` span), in the window."""


def read(ctx):
    from stats import percentile
    return percentile(ctx.hist("engine.decode_chunk_s"), 50)
