"""A whole run on the CPU (past the harness's look for a chip) with the
timed path broken underneath: ``correct`` has to come out false, once for
each fault a one-chip serving cell can have."""
import contextlib
import io
import json

import jax
import jax.numpy as jnp
import pytest

import run


def token_altered(engine):
    """Each decode dispatch's newest token per slot is replaced where the
    engine emits it."""
    orig = engine._dispatch_decode
    vocab = engine.cfg.vocab_size

    def dispatch(runnable, stalled):
        orig(runnable, stalled)
        for slot in runnable:
            if slot.tokens:
                slot.tokens[-1] = (slot.tokens[-1] + 1) % vocab
    engine._dispatch_decode = dispatch


def state_unchanged(engine):
    """Decode steps hand back the KV pool they were given: the keys and
    values of decoded tokens are never kept."""
    orig = engine._dispatch_decode

    def dispatch(runnable, stalled):
        before = jax.tree.map(jnp.copy, engine.pool)
        orig(runnable, stalled)
        engine.pool = before
    engine._dispatch_decode = dispatch


def result(cell, fault, seed=2**33 + 9):
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.run(cell, seed=seed, seconds=1.5, trace=False,
                     require_tpu=False, fault=fault)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(cell_factory):
    assert result(cell_factory(), None)["correct"] is True


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(cell_factory, fault):
    line = result(cell_factory(), fault)
    assert line["correct"] is False
    gap = line["check"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
