"""From a configuration file to the module of its architecture.

Each module under ``models/`` serves the ``model_type`` values it names in
``MODEL_TYPES`` and provides, for a configuration of one of them:

* ``READS``: the configuration keys it reads;
* ``program_fields(conf)``: the program's ``ArchConfig`` fields as plain
  data; ``attention``, ``moe`` and ``compression`` hold the fields of
  ``AttentionConfig``, ``MoEConfig`` and ``CompressionConfig``
  (``program.py`` builds the dataclasses);
* ``shapes_tree(conf)`` and ``leaf_std(path, shape)``: the weight tree's
  leaf shapes, in the program's parameter layout, and each leaf's scale
  (``weights.py`` draws them);
* ``logits(params, conf, tokens, pos, low)``: the plain reference's
  logits at positions ``pos`` of sequences ``tokens``, with ``low`` the
  fp8 control (``reference.py`` jits and reduces them);
* ``prefill_flops(conf, prompt_len)``, ``decode_flops(conf, context)``,
  ``kv_read_bytes(conf, context)`` and ``kernel_flops(conf, context)``:
  the work counts (``work.py``).

The modules are found by scanning ``models/``, so a new architecture is a
new file there.  A configuration key that neither its module nor the
harness reads is an error: a mechanism is never dropped in silence.
"""
from __future__ import annotations

import functools
import importlib
import pathlib

MODELS = pathlib.Path(__file__).resolve().parent / "models"

# Keys the harness reads itself, or that describe the file rather than
# the model: ``served`` holds how the program serves it, ``torch_dtype``
# names the published checkpoint's dtype, ``max_position_embeddings``
# bounds a cell's longest sequence (``run.load_cell``).
HARNESS_KEYS = frozenset({"name", "source", "model_type", "reduced",
                          "assumed", "served", "torch_dtype",
                          "max_position_embeddings"})


@functools.cache
def _by_type() -> dict:
    out = {}
    for path in sorted(MODELS.glob("*.py")):
        if path.stem.startswith("_"):
            continue
        mod = importlib.import_module(f"models.{path.stem}")
        for t in mod.MODEL_TYPES:
            if t in out:
                raise ValueError(f"model type {t!r} is served by both "
                                 f"{out[t].__name__} and {mod.__name__}")
            out[t] = mod
    return out


def module(conf: dict):
    """The module that serves ``conf["model_type"]``, once the
    configuration's keys are all read by it or by the harness."""
    known = _by_type()
    t = conf.get("model_type")
    if t not in known:
        raise ValueError(f"no module in {MODELS.name}/ serves model_type "
                         f"{t!r}; known: {sorted(known)}")
    mod = known[t]
    unread = sorted(set(conf) - HARNESS_KEYS - set(mod.READS))
    if unread:
        raise ValueError(f"configuration {conf.get('name')!r} has keys that "
                         f"{mod.__name__} does not read: {unread}")
    return mod
