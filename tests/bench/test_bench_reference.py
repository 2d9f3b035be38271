"""The plain reference against the continuous engine's own programs at a
tiny size on the CPU: the prefill-and-pack program's first token, then
paged decode steps through the block table, teacher-forced with the
engine's own tokens.  Both configurations' mechanisms are covered: q/k
norm (qwen3) and QKV bias (qwen2.5).

Tolerance: the engine runs the circulant projections through f32 DFT
matmuls and the reference through materialized dense blocks at highest
precision, so the two round differently at f32 (~1e-6 relative per op);
over two layers and a 1024-way head that stays below 1e-3 of the logit
scale.  Leaving out a mechanism moves logits by a good part of their
scale, which the second test shows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import reference
import weights
from bench_tiny import tiny_conf

PAGE = 8
TOL = 1e-3          # max |engine - reference| as a share of max |logit|


def engine_logits(conf, params, prompt, steps):
    """First token of the prefill program, then ``steps`` paged decode
    steps' logit rows, each fed the engine's own greedy token."""
    from repro.models.registry import build_model
    from repro.serve import decode as dec
    from repro.serve import kvcache as kvc
    from repro.serve.params import precompute_serving_params
    cfg = program.arch_config(conf)
    sp = precompute_serving_params(params, cfg)
    model = build_model(cfg)
    S = len(prompt)
    maxp = kvc.pages_for(S + steps, PAGE)
    pool = kvc.build_pool(cfg, maxp + 1, PAGE)
    table = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None]
    n_pages = kvc.pages_for(S, PAGE)
    padded = np.zeros(n_pages * PAGE, np.int32)
    padded[:S] = prompt
    tok, ok, pool, _ = jax.jit(dec.make_prefill_pack_step(
        cfg, n_pages, PAGE))(sp, {"tokens": jnp.asarray(padded[None])},
                             pool, table[0, :n_pages], jnp.int32(S))
    assert bool(ok)
    tokens, rows = [int(tok)], []
    step = jax.jit(lambda p, t, c, pos: model.decode_step(
        p, t, c, pos, block_table=table))
    for j in range(steps):
        lg, pool = step(sp, jnp.asarray([[tokens[-1]]], jnp.int32), pool,
                        jnp.asarray([S + j], jnp.int32))
        rows.append(np.asarray(lg[0, -1], np.float32))
        tokens.append(int(np.argmax(rows[-1])))
    return tokens, np.stack(rows)


def reference_rows(conf, params, prompt, tokens):
    """The reference's logits where the engine chose ``tokens``."""
    S, V = len(prompt), conf["vocab_size"]
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    pos = (S - 1 + np.arange(len(tokens)))[None]
    ids = np.broadcast_to(np.arange(V, dtype=np.int32),
                          (1, len(tokens), V))
    _, top, rows = reference.logit_rows(params, conf, seq[None], pos, ids)
    return np.asarray(top)[0], np.asarray(rows)[0]


@pytest.mark.parametrize("qk_norm,qkv_bias", [(True, False), (False, True)],
                         ids=["qk_norm", "qkv_bias"])
def test_reference_matches_prefill_and_paged_decode(qk_norm, qkv_bias):
    conf = tiny_conf(qk_norm, qkv_bias)
    params = weights.make_params(conf, 2**33 + 17)
    prompt = np.random.default_rng(5).integers(
        1, conf["vocab_size"], size=21).astype(np.int32)
    tokens, rows = engine_logits(conf, params, prompt, 6)
    top, ref = reference_rows(conf, params, prompt, tokens)
    scale = np.abs(ref).max()
    assert top[0] == tokens[0]                 # the prefill's greedy token
    err = np.abs(rows - ref[1:]).max() / scale
    assert err < TOL, err
    assert list(top[1:]) == tokens[1:]


@pytest.mark.parametrize("mechanism", ["qk_norm", "qkv_bias"])
def test_reference_sees_each_mechanism(mechanism):
    conf = tiny_conf(mechanism == "qk_norm", mechanism == "qkv_bias")
    params = weights.make_params(conf, 4)
    prompt = np.random.default_rng(6).integers(
        1, conf["vocab_size"], size=21).astype(np.int32)
    tokens, rows = engine_logits(conf, params, prompt, 4)
    without = dict(conf, **{mechanism: False})
    if mechanism == "qkv_bias":                # the tree keeps its biases:
        params = jax.tree_util.tree_map_with_path(   # zero them instead
            lambda p, x: jnp.zeros_like(x) if p[-1].key == "b" else x,
            params)
        without = conf
    _, ref = reference_rows(without, params, prompt, tokens)
    err = np.abs(rows - ref[1:]).max() / np.abs(ref).max()
    assert err > 100 * TOL, err
