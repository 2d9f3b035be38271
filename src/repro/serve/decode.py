"""Serving step builders: prefill, single-token decode, and the
device-resident multi-token decode loop.

These are the functions the dry-run lowers for the ``prefill_*`` /
``decode_*`` / ``long_*`` cells, and the engine jit-calls for real serving.
The decode step donates the cache (in-place ring-buffer update — the paper's
in-place activation memory, as XLA buffer donation).  ``make_decode_loop``
wraps the step in a ``lax.while_loop`` so one dispatch decodes every token of
a batch — the host round-trip per token is what dominated the seed engine.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models.registry import Model, build_model

# -- dispatch-kind names (obs/prof.py attribution units) --------------------
# One vocabulary for what the engines dispatch, shared by the profiler's
# histogram labels, stats()["roofline"] keys, and the Chrome-trace lanes.
DECODE_CHUNK_KIND = "decode_chunk"


def prefill_kind(n_pages: int) -> str:
    """Continuous engine: one prefill program per page bucket."""
    return f"prefill_{n_pages}p"


def batch_prefill_kind(batch: int, seq: int) -> str:
    """Batch engine: prefill recompiles per (B, padded S)."""
    return f"prefill_b{batch}_s{seq}"


def batch_decode_kind(steps: int, batch: int) -> str:
    """Batch engine: one scanned decode loop per (step budget, B)."""
    return f"decode_loop_s{steps}_b{batch}"


def make_prefill_step(cfg: ArchConfig, logits_sharding=None) -> Callable:
    model = build_model(cfg)

    def prefill_step(params, batch, cache) -> Tuple[jax.Array, Any]:
        logits, new_cache = model.prefill(params, batch, cache)
        if logits_sharding is not None:
            logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
        # return only last-position logits: serving samples the next token
        return logits[:, -1:], new_cache
    return prefill_step


def make_decode_step(cfg: ArchConfig, sample: bool = False,
                     temperature: float = 1.0,
                     logits_sharding=None, seed: int = 0) -> Callable:
    """Single-token decode step.  ``seed`` keys the sampling PRNG (folded
    with the cache position), so sampled generations are reproducible per
    engine and distinct across engines with different seeds."""
    model = build_model(cfg)
    base_key = jax.random.PRNGKey(seed)

    def decode_step(params, tokens, cache, cache_pos):
        logits, new_cache = model.decode_step(params, tokens, cache,
                                              cache_pos)
        if logits_sharding is not None:
            logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
        with jax.named_scope("sample"):
            if sample:
                key = jax.random.fold_in(base_key, cache_pos)
                nxt = jax.random.categorical(
                    key, logits[:, -1].astype(jnp.float32) / temperature, -1)
            else:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
        return logits, nxt.astype(jnp.int32), new_cache
    return decode_step


def make_decode_loop(cfg: ArchConfig, steps: int, *, sample: bool = False,
                     temperature: float = 1.0, eos_id: Optional[int] = None,
                     logits_sharding=None, seed: int = 0) -> Callable:
    """Device-resident multi-token decode: one dispatch for ``steps`` tokens.

    The per-token step above runs inside a ``lax.while_loop`` whose carry
    holds (step index, token buffer, current token, cache, done mask) — the
    cache is threaded through the loop and donated at the jit boundary, so
    decode stays a single in-place device program instead of ``steps``
    host-round-tripped dispatches.

    Per-request lengths are honored ON DEVICE: ``lengths[i]`` freezes request
    ``i`` after its budget (its slots hold ``eos_id``/0 and its carry token
    stops advancing); with ``eos_id`` set, a request also freezes after
    emitting EOS.  The loop exits EARLY once every request is done — with no
    EOS and uniform lengths it runs the full trip and emits bit-identical
    tokens to the per-token loop (greedy; tested per arch).

    Returns ``decode_loop(params, first_tok, cache, pos0, lengths)`` ->
    ``(tokens (B, steps) int32, cache)``; ``first_tok`` is the prefill's
    sampled token (slot 0 of the buffer), ``pos0`` the prompt length.
    """
    step = make_decode_step(cfg, sample=sample, temperature=temperature,
                            logits_sharding=logits_sharding, seed=seed)
    fill = 0 if eos_id is None else int(eos_id)

    def decode_loop(params, first_tok, cache, pos0, lengths):
        B = first_tok.shape[0]
        first = jnp.where(lengths > 0, first_tok, jnp.int32(fill))
        buf = jnp.full((B, steps), fill, jnp.int32).at[:, 0].set(first)
        done = lengths <= 1
        if eos_id is not None:
            done = done | (first_tok == eos_id)

        def cond_fn(st):
            j, _, _, _, done_ = st
            return jnp.logical_and(j < steps, ~jnp.all(done_))

        def body_fn(st):
            j, buf_, cur, cache_, done_ = st
            _, nxt, cache_ = step(params, cur[:, None], cache_, pos0 + j - 1)
            tok = jnp.where(done_, jnp.int32(fill), nxt)
            buf_ = jax.lax.dynamic_update_slice(buf_, tok[:, None], (0, j))
            nd = done_ | (j + 1 >= lengths)
            if eos_id is not None:
                nd = nd | (nxt == eos_id)
            cur = jnp.where(done_, cur, nxt)
            return (j + 1, buf_, cur, cache_, nd)

        state = (jnp.int32(1), buf, first_tok, cache, done)
        _, buf, _, cache, _ = jax.lax.while_loop(cond_fn, body_fn, state)
        return buf, cache
    return decode_loop


# ---------------------------------------------------------------------------
# Device-side numerics capture (repro.obs.health)
# ---------------------------------------------------------------------------
def logit_stats(lg):
    """``(..., V)`` logits -> ``(..., 4)`` cheap health reductions:
    ``[absmax, softmax entropy, top1-top2 margin, non-finite count]``.

    One extra pass over a logit row per step — noise next to the matmuls
    that produced it (the same budget argument as the NaN guard, which
    is the degenerate binary form of column 3).  Rows containing
    non-finite values yield non-finite absmax/entropy/margin; consumers
    (``obs/health.py``) key on column 3 and skip the rest."""
    r = lg.astype(jnp.float32)
    nonf = jnp.sum(~jnp.isfinite(r), axis=-1).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(r), axis=-1)
    m = jnp.max(r, axis=-1, keepdims=True)
    z = r - m
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    p = jnp.exp(z - lse[..., None])
    ent = lse - jnp.sum(p * z, axis=-1)
    # top-2 margin WITHOUT lax.top_k (a full sort on CPU, ~20x the cost
    # of every other reduction here combined): mask exactly the argmax
    # position and re-max — tie semantics identical to top_k (margin 0)
    idx = jnp.argmax(r, axis=-1)
    vocab = jax.lax.broadcasted_iota(jnp.int32, r.shape, r.ndim - 1)
    r2 = jnp.where(vocab == idx[..., None], -jnp.inf, r)
    margin = m[..., 0] - jnp.max(r2, axis=-1)
    return jnp.stack([absmax, ent, margin, nonf], axis=-1)


def cache_group_absmax(cache):
    """Per-layer-group activation absmax over a dense cache's K/V leaves.

    The prefill cache is the one place every layer group's activations
    are already materialized (the paged pool only ever holds quantized
    pages), so prefill dispatches carry this fixed-shape vector out as a
    health side-output: a datapath drifting toward overflow marches up
    the ``health.act_absmax`` buckets layers before logits go non-finite."""
    out = []

    def walk(node):
        if isinstance(node, dict) and "k" in node and "v" in node:
            for key in ("k", "v"):
                leaf = node[key]
                out.append(jnp.max(jnp.abs(leaf.astype(jnp.float32)),
                                   axis=tuple(range(1, leaf.ndim))))
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(cache)
    if not out:
        return jnp.zeros((1,), jnp.float32)
    return jnp.concatenate([jnp.atleast_1d(a) for a in out])


# ---------------------------------------------------------------------------
# Paged continuous-batching builders (serve/kvcache.py + serve/scheduler.py)
# ---------------------------------------------------------------------------
def make_prefill_pack_step(cfg: ArchConfig, n_pages: int,
                           page_size: int,
                           capture_stats: bool = False) -> Callable:
    """B=1 exact-position prefill + page scatter, one dispatch per admission.

    The prompt is right-padded to ``n_pages * page_size`` (a page-aligned
    bucket, so a handful of page counts cover every prompt length — no
    per-length recompiles).  Padding sits AFTER the prompt: causal masking
    keeps positions < S bit-exact vs. an unpadded prefill, and the garbage
    cache tail stays masked until decode overwrites it (position validity is
    ``i <= slot position``).

    Returns ``prefill_pack(params, batch, pool, pages, true_len)`` ->
    ``(first_token scalar int32, ok scalar bool, pool, stats)`` — the first
    token is the greedy argmax at the prompt's true last position (same op
    the batch engine runs on its prefill logits); ``ok`` is a cheap
    device-side finiteness check on those logits (False = the slot is
    poisoned and the engine retires it FAILED instead of streaming
    garbage).

    With ``capture_stats`` (the obs-enabled engines) ``stats`` is ONE
    flat fixed-shape f32 vector of health reductions —
    ``[logit_stats(4) | kv_clipped | kv_total | act_absmax per layer
    group]`` — packed device-side so the host pays a single transfer per
    prefill (four small device_gets per dispatch showed up in the
    obs_overhead budget); the engine slices it and hands
    ``obs/health.py`` the pieces after the fence.  Without it ``stats``
    is None and the compiled program is byte-identical to the pre-health
    one (the disabled arm of the ``obs_overhead`` bench stays honest).
    """
    from . import kvcache as kvc
    model = build_model(cfg)
    spad = n_pages * page_size

    def prefill_pack(params, batch, pool, pages, true_len):
        cache = model.init_cache(1, spad, dtype=jnp.float32)
        logits, dense = model.prefill(params, batch, cache)
        with jax.named_scope("sample"):
            last = jax.lax.dynamic_index_in_dim(logits[0], true_len - 1, 0,
                                                keepdims=False)
            ok = jnp.all(jnp.isfinite(last))
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        stats = None
        with jax.named_scope("kv_write"):
            if capture_stats:
                pool, clipped, total = kvc.pack_prefill_cache(
                    pool, dense, pages, page_size, true_len=true_len,
                    with_stats=True)
            else:
                pool = kvc.pack_prefill_cache(pool, dense, pages, page_size,
                                              true_len=true_len)
        if capture_stats:
            with jax.named_scope("health"):
                stats = jnp.concatenate([
                    logit_stats(last),
                    jnp.stack([jnp.asarray(clipped, jnp.float32),
                               jnp.asarray(total, jnp.float32)]),
                    cache_group_absmax(dense)])
        return nxt, ok, pool, stats
    return prefill_pack


def make_paged_decode_loop(cfg: ArchConfig, chunk: int, *,
                           sample: bool = False, temperature: float = 1.0,
                           eos_id: Optional[int] = None, seed: int = 0,
                           logits_sharding=None,
                           paged_impl: str = "stream",
                           nan_guard: bool = True,
                           capture_stats: bool = False) -> Callable:
    """Device-resident decode over paged slots: one dispatch per ``chunk``.

    The carry holds per-slot (token, position, remaining budget, done) —
    every slot advances at ITS OWN position (RoPE + mask + page writes are
    per-slot), so slots admitted at different times decode together in one
    program.  A slot freezes when its budget hits zero or it emits
    ``eos_id``; its writes route to the trash page (position -1) and its
    buffer slots hold ``eos_id``/0.  The loop exits early once every slot
    is frozen; the scheduler retires/refills slots between dispatches.

    ``paged_impl`` selects the attention lowering inside the step:
    "stream" (default) runs the fused paged flash-decode — pool pages
    stream through online-softmax, so the loop's peak memory no longer
    carries a ``(B, maxp * page, Hkv, D)`` gathered KV view per layer;
    "gather" keeps the PR 3 materialized-view path as the parity oracle.
    Either way the stacked pool rides this loop's carry and, inside each
    step, the layer scan's carry (models/transformer.py): layers write and
    read it at ``(layer, page)`` in place, so the carry stays aliased to
    the donated pool and the program holds it once (the engine reports
    the program's temporaries as ``engine.decode_temp_bytes``).

    With ``nan_guard`` (default) the step checks its last-position logits
    for NaN/Inf ON DEVICE (one ``isfinite`` reduce over the logit row —
    noise next to the matmuls).  A non-finite slot freezes exactly like an
    EOS slot (no token appended, position/budget stop advancing, writes
    route to the trash page) and is flagged in the returned ``anom`` mask
    so the engine retires it with status FAILED instead of streaming
    garbage tokens.

    Returns ``decode_loop(params, cur, pool, table, pos, rem)`` ->
    ``(buf (B, chunk) int32, cur, pool, pos, rem, done, anom, stats)``.

    With ``capture_stats``, ``stats`` is a ``(B, 4)`` float32 row per
    slot — ``[logit absmax, entropy, top1-margin, non-finite step count]``
    (``logit_stats`` columns).  Columns 0–2 are SAMPLED once per
    dispatch: the loop carries each slot's latest finite-step logit row
    (a masked 12 KB copy per step — noise) and the reductions run ONCE
    on it AFTER the ``while_loop``.  Computing them per step cost ~9% of
    the decode program, and hiding them behind an in-loop ``lax.cond``
    did not help (XLA rewrites small conditionals inside loops into
    both-branch selects).  Column 3 stays exact and per-step: it
    accumulates the NaN guard's ``bad`` mask, which the program computes
    every step regardless, so the ``anom`` mask remains the thresholded
    view of this column and anomalies surface on the exact dispatch they
    occur.  The carried row is gated on ``finite & ~halt``, so a
    poisoned step can never corrupt the sample.  Idle/never-advanced
    slots keep an all-zero carried row (margin +inf after reduction);
    the engine skips rows that took no step.  Without ``capture_stats``,
    ``stats`` is None and the compiled loop is unchanged.

    Telemetry contract (repro.obs): dispatch is async, so the engine
    fences the loop outputs (``jax.block_until_ready``) before stamping a
    span boundary — the ``engine.decode_chunk_s`` histogram and the
    per-chunk trace marks measure this device program, not its dispatch.
    """
    model = build_model(cfg)
    base_key = jax.random.PRNGKey(seed)
    fill = 0 if eos_id is None else int(eos_id)

    def step(params, cur, pool, pos_masked, table):
        logits, pool = model.decode_step(params, cur[:, None], pool,
                                         pos_masked, block_table=table,
                                         paged_impl=paged_impl)
        if logits_sharding is not None:
            logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
        lastlg = logits[:, -1] if capture_stats else None
        with jax.named_scope("sample"):
            finite = (jnp.all(jnp.isfinite(logits[:, -1]), axis=-1)
                      if nan_guard else jnp.ones(cur.shape[0], bool))
            if sample:
                # fold in slot index AND position: slots at the same
                # position (e.g. identical prompts admitted together) must
                # not draw from identical PRNG noise
                slots = jnp.arange(cur.shape[0])
                keys = jax.vmap(lambda s, p: jax.random.fold_in(
                    jax.random.fold_in(base_key, s), p))(
                    slots, jnp.maximum(pos_masked, 0))
                nxt = jax.vmap(lambda k, lg: jax.random.categorical(
                    k, lg.astype(jnp.float32) / temperature, -1))(
                    keys, logits[:, -1])
            else:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
        return nxt.astype(jnp.int32), finite, pool, lastlg

    def decode_loop(params, cur, pool, table, pos, rem):
        B = cur.shape[0]
        done0 = rem <= 0
        anom0 = jnp.zeros(B, bool)
        buf = jnp.full((B, chunk), fill, jnp.int32)
        # carry = (latest finite-step logit row, per-step nonfinite count);
        # the reductions run once AFTER the loop (docstring)
        stats0 = ((jnp.zeros((B, cfg.vocab_size), jnp.float32),
                   jnp.zeros((B,), jnp.float32))
                  if capture_stats else None)

        def cond_fn(st):
            return jnp.logical_and(st[0] < chunk, ~jnp.all(st[6]))

        def body_fn(st):
            j, buf_, cur_, pool_, pos_, rem_, done_, anom_, stats_ = st
            masked = jnp.where(done_, -1, pos_)
            nxt, finite, pool_, lastlg = step(params, cur_, pool_, masked,
                                              table)
            # a poisoned slot freezes like EOS: no token, no advance — the
            # bad logits never pick a token and the slot retires FAILED
            bad = ~done_ & ~finite
            halt = done_ | bad
            if capture_stats:
                lastrow, nonf = stats_
                # keep the latest FINITE active row per slot (a poisoned
                # row never lands in the sample); non-finite accounting
                # is exact because ``bad`` rides the per-step NaN guard
                with jax.named_scope("health"):
                    upd = (~halt & finite)[:, None]
                    lastrow = jnp.where(upd, lastlg.astype(jnp.float32),
                                        lastrow)
                    stats_ = (lastrow, nonf + bad.astype(jnp.float32))
            tok = jnp.where(halt, jnp.int32(fill), nxt)
            buf_ = jax.lax.dynamic_update_slice(buf_, tok[:, None], (0, j))
            pos_ = jnp.where(halt, pos_, pos_ + 1)
            rem_ = jnp.where(halt, rem_, rem_ - 1)
            nd = halt | (rem_ <= 0)
            if eos_id is not None:
                nd = nd | (~halt & (nxt == eos_id))
            cur_ = jnp.where(halt, cur_, nxt)
            return (j + 1, buf_, cur_, pool_, pos_, rem_, nd,
                    anom_ | bad, stats_)

        st = (jnp.int32(0), buf, cur, pool, pos, rem, done0, anom0, stats0)
        _, buf, cur, pool, pos, rem, done, anom, stats = jax.lax.while_loop(
            cond_fn, body_fn, st)
        if capture_stats:
            lastrow, nonf = stats
            with jax.named_scope("health"):
                stats = logit_stats(lastrow).at[:, 3].set(nonf)
        return buf, cur, pool, pos, rem, done, anom, stats
    return decode_loop
