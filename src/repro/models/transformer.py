"""Unified decoder-LM covering the dense / MoE / hybrid / SSM assigned archs.

A model is a list of *segments*; each segment is a repeating ``pattern`` of
block kinds scanned ``n`` times (params stacked over the scan axis).  This
keeps HLO size O(pattern) instead of O(layers) while allowing heterogeneous
stacks (gemma2 local/global alternation, recurrentgemma rec-rec-attn,
llama4 dense/MoE interleave, xlstm mlstm/slstm mixes — including non-divisible
tails like recurrentgemma's 26 = 8x(rec,rec,attn) + 1x(rec,rec)).

Block kinds:
  attn        global causal attention + dense MLP
  attn_local  sliding-window attention + dense MLP
  moe         global attention + mixture-of-experts
  moe_swa     sliding-window attention + MoE (mixtral)
  rec         RG-LRU temporal block + dense MLP (recurrentgemma)
  mlstm/slstm xLSTM blocks (self-contained, no separate MLP)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..dist.ctx import shard_act
from ..layers import attention as attn_lib
from ..layers import embeddings as emb_lib
from ..layers import ffn as ffn_lib
from ..layers import norms as norm_lib
from ..layers import recurrent as rec_lib

ATTN_KINDS = ("attn", "attn_local", "moe", "moe_swa")


def segments_for(cfg: ArchConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """Segment plan for an arch (pattern, repeat) — see registry for sources."""
    pat = cfg.recurrent.pattern
    if pat:                                   # hybrid / ssm archs define theirs
        period = len(pat)
        n, rem = divmod(cfg.num_layers, period)
        segs = [(tuple(pat), n)] if n else []
        if rem:
            segs.append((tuple(pat[:rem]), 1))
        return segs
    if cfg.moe.num_experts:
        if cfg.moe.interleave > 1:
            pat = tuple(["attn", "moe"] * (cfg.moe.interleave // 2))
        else:
            pat = ("moe_swa",) if cfg.attention.layout == "sliding" else ("moe",)
    elif cfg.attention.layout == "alternating":
        pat = ("attn_local", "attn")
    elif cfg.attention.layout == "sliding":
        pat = ("attn_local",)
    else:
        pat = ("attn",)
    period = len(pat)
    n, rem = divmod(cfg.num_layers, period)
    segs = [(tuple(pat), n)] if n else []
    if rem:
        segs.append((tuple(pat[:rem]), 1))
    return segs


def _window_for(kind: str, cfg: ArchConfig) -> int:
    if kind in ("attn_local", "moe_swa"):
        return cfg.attention.sliding_window
    return 0


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------
def init_block(key, kind: str, cfg: ArchConfig) -> Dict:
    d, dff = cfg.d_model, cfg.d_ff
    comp = cfg.compression
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": norm_lib.init_norm(cfg.norm, d)}
    if kind in ATTN_KINDS:
        p["attn"] = attn_lib.init_attention(ks[0], cfg, d, comp)
        p["ln2"] = norm_lib.init_norm(cfg.norm, d)
        if kind in ("moe", "moe_swa"):
            p["moe"] = ffn_lib.init_moe(ks[1], d, dff, cfg.moe, comp)
        else:
            p["mlp"] = ffn_lib.init_mlp(ks[1], d, dff, comp)
        if getattr(cfg, "sandwich_norm", False) or cfg.name.startswith("gemma2"):
            p["ln1_post"] = norm_lib.init_norm(cfg.norm, d)
            p["ln2_post"] = norm_lib.init_norm(cfg.norm, d)
    elif kind == "rec":
        width = cfg.recurrent.lru_width or d
        p["rec"] = rec_lib.init_rglru(ks[0], d, width, comp,
                                      cfg.recurrent.conv1d_width)
        p["ln2"] = norm_lib.init_norm(cfg.norm, d)
        p["mlp"] = ffn_lib.init_mlp(ks[1], d, dff, comp)
    elif kind == "mlstm":
        p["cell"] = rec_lib.init_mlstm(ks[0], d, cfg.recurrent.mlstm_heads,
                                       cfg.recurrent.proj_factor, comp)
    elif kind == "slstm":
        p["cell"] = rec_lib.init_slstm(ks[0], d, cfg.recurrent.mlstm_heads, comp)
    else:
        raise ValueError(kind)
    return p


def apply_block(params, x, kind: str, cfg: ArchConfig, *, mode: str,
                cache=None, cache_pos=None, q_chunk: int, kv_chunk: int,
                block_table=None, layer=None, paged_impl: str = "stream"):
    """Returns (x, new_cache, aux).  Paged decode passes the block's whole
    stacked pool leaf as ``cache`` and its stack index as ``layer``."""
    comp = cfg.compression
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    if kind in ATTN_KINDS:
        with jax.named_scope("attention"):
            h = norm_lib.apply_norm(cfg.norm, params["ln1"], x)
            a, new_cache = attn_lib.attention_block(
                params["attn"], h, cfg=cfg, causal=True,
                window=_window_for(kind, cfg), cache=cache,
                cache_pos=cache_pos, mode=mode, q_chunk=q_chunk,
                kv_chunk=kv_chunk, block_table=block_table, layer=layer,
                paged_impl=paged_impl)
            if "ln1_post" in params:
                a = norm_lib.apply_norm(cfg.norm, params["ln1_post"], a)
            x = x + a
        moe = kind in ("moe", "moe_swa")
        with jax.named_scope("moe" if moe else "mlp"):
            h = norm_lib.apply_norm(cfg.norm, params["ln2"], x)
            if moe:
                f, aux = ffn_lib.moe(params["moe"], h, d_ff=cfg.d_ff,
                                     moe_cfg=cfg.moe, comp=comp,
                                     activation=cfg.ffn_activation, mode=mode)
            else:
                f = ffn_lib.mlp(params["mlp"], h, d_ff=cfg.d_ff, comp=comp,
                                activation=cfg.ffn_activation, mode=mode)
            if "ln2_post" in params:
                f = norm_lib.apply_norm(cfg.norm, params["ln2_post"], f)
            x = x + f
    elif kind == "rec":
        width = cfg.recurrent.lru_width or cfg.d_model
        h = norm_lib.apply_norm(cfg.norm, params["ln1"], x)
        r, new_cache = rec_lib.rglru_block(params["rec"], h, width=width,
                                           comp=comp, mode=mode, state=cache)
        x = x + r
        h = norm_lib.apply_norm(cfg.norm, params["ln2"], x)
        x = x + ffn_lib.mlp(params["mlp"], h, d_ff=cfg.d_ff, comp=comp,
                            activation=cfg.ffn_activation, mode=mode)
    elif kind == "mlstm":
        h = norm_lib.apply_norm(cfg.norm, params["ln1"], x)
        y, new_cache = rec_lib.mlstm_block(
            params["cell"], h, heads=cfg.recurrent.mlstm_heads,
            proj_factor=cfg.recurrent.proj_factor, comp=comp, mode=mode,
            state=cache, chunk=cfg.mlstm_chunk)
        x = x + y
    elif kind == "slstm":
        h = norm_lib.apply_norm(cfg.norm, params["ln1"], x)
        y, new_cache = rec_lib.slstm_block(params["cell"], h, comp=comp,
                                           mode=mode, state=cache)
        x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------
def init_params(key, cfg: ArchConfig) -> Dict:
    segs = segments_for(cfg)
    keys = jax.random.split(key, len(segs) + 2)
    params: Dict[str, Any] = {
        "embed": emb_lib.init_embedding(keys[0], cfg.padded_vocab(), cfg.d_model),
        "final_norm": norm_lib.init_norm(cfg.norm, cfg.d_model),
        "segments": [],
    }
    if cfg.max_position:
        params["pos"] = emb_lib.init_learned_pos(keys[1], cfg.max_position,
                                                 cfg.d_model)
    for si, (pattern, n) in enumerate(segs):
        seg_keys = jax.random.split(keys[2 + si], n)

        def one_group(k):
            ks = jax.random.split(k, len(pattern))
            return tuple(init_block(ks[i], kind, cfg)
                         for i, kind in enumerate(pattern))

        groups = [one_group(k) for k in seg_keys]
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *groups)
        params["segments"].append(stacked)
    return params


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> List:
    """Per-segment stacked caches (leading dim = groups in segment)."""
    segs = segments_for(cfg)
    caches = []
    for pattern, n in segs:
        def one_group():
            out = []
            for kind in pattern:
                if kind in ATTN_KINDS:
                    out.append(attn_lib.init_kv_cache(
                        batch, max_seq, cfg, _window_for(kind, cfg), dtype))
                elif kind == "rec":
                    width = cfg.recurrent.lru_width or cfg.d_model
                    out.append(rec_lib.init_rglru_state(
                        batch, width, cfg.recurrent.conv1d_width))
                elif kind == "mlstm":
                    d_in = int(cfg.d_model * cfg.recurrent.proj_factor)
                    out.append(rec_lib.init_mlstm_state(
                        batch, cfg.recurrent.mlstm_heads,
                        d_in // cfg.recurrent.mlstm_heads))
                elif kind == "slstm":
                    out.append(rec_lib.init_slstm_state(batch, cfg.d_model))
            return tuple(out)
        g = one_group()
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n, *x.shape)), g))
    return caches


def forward(params, tokens, cfg: ArchConfig, *, mode: str = "train",
            cache: Optional[List] = None, cache_pos=None,
            frontend_embeds=None, q_chunk: Optional[int] = None,
            kv_chunk: Optional[int] = None, block_table=None,
            paged_impl: str = "stream"):
    """tokens: (B, S) int32.  Returns (logits, aux, new_cache).

    With ``block_table`` set, ``cache`` is a paged pool tree (attention
    leaves {"k","v"} shaped (n, P, page, Hkv, D)) and ``cache_pos`` is the
    per-slot (B,) position vector — see serve/kvcache.py.  The pool then
    rides the layer scan's CARRY, not its xs/ys: each group gets its index
    (``jnp.arange(n)`` as xs, a python int when unrolled) and writes and
    reads its pages of the stacked leaves in place, so XLA neither slices a
    layer's slab out of the stack nor restacks it, and the pool is held
    once.  ``paged_impl`` selects the paged attention lowering ("stream"
    fused flash-decode / "gather" legacy materialized view — see
    layers/attention.py).
    """
    q_chunk = q_chunk or cfg.attn_q_chunk
    kv_chunk = kv_chunk or cfg.attn_kv_chunk
    segs = segments_for(cfg)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    with jax.named_scope("embed"):
        x = emb_lib.embed(params["embed"], tokens, scale_by_dim=cfg.name
                          .startswith(("gemma", "recurrent")))
        x = x.astype(dtype)
        if frontend_embeds is not None:
            # modality stub: precomputed patch/frame embeddings replace the
            # first `num_patches` token slots (DESIGN.md
            # §Arch-applicability).
            np_ = frontend_embeds.shape[1]
            x = jnp.concatenate([frontend_embeds.astype(dtype),
                                 x[:, np_:]], axis=1)
        if "pos" in params:
            pos0 = 0 if cache_pos is None else cache_pos
            S = x.shape[1]
            table = params["pos"]["pos"]
            idx = pos0 + jnp.arange(S)
            x = x + table[idx].astype(dtype)[None]

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: List = []
    with jax.named_scope("layers"):
        for si, (pattern, n) in enumerate(segs):
            seg_params = params["segments"][si]
            seg_cache = None if cache is None else cache[si]

            def run_group(x_, aux_, gp, gc, layer):
                new_gc = []
                for bi, kind in enumerate(pattern):
                    bp = gp[bi]
                    c_in = None if gc is None else gc[bi]
                    x_ = shard_act(x_)          # block-boundary sharding pin
                    x_, c_out, aux_b = apply_block(
                        bp, x_, kind, cfg, mode=mode, cache=c_in,
                        cache_pos=cache_pos, q_chunk=q_chunk,
                        kv_chunk=kv_chunk, block_table=block_table,
                        layer=layer, paged_impl=paged_impl)
                    new_gc.append(c_out)
                    aux_ = aux_ + aux_b
                x_ = shard_act(x_)
                return x_, aux_, (tuple(new_gc) if gc is not None else 0)

            def group_fn(carry, xs):
                gp, gc = xs
                x_, aux_, new_gc = run_group(*carry, gp, gc, None)
                return (x_, aux_), new_gc

            def paged_group_fn(carry, xs):
                x_, aux_, pool_ = carry
                gp, layer = xs
                return run_group(x_, aux_, gp, pool_, layer), None

            if cfg.remat == "full" and mode == "train":
                group_fn = jax.checkpoint(
                    group_fn, policy=jax.checkpoint_policies.nothing_saveable)
            paged = block_table is not None and seg_cache is not None
            if cfg.unroll_scan:
                # python loop over groups: exact cost_analysis / collective
                # counts for the roofline lowering (a while body is costed
                # once)
                outs = []
                for g in range(n):
                    gp = jax.tree.map(lambda a: a[g], seg_params)
                    if paged:                   # the same carried pool
                        x, aux_total, seg_cache = run_group(
                            x, aux_total, gp, seg_cache, g)
                    else:
                        gc = (None if seg_cache is None else
                              jax.tree.map(lambda a: a[g], seg_cache))
                        (x, aux_total), new_gc = group_fn((x, aux_total),
                                                          (gp, gc))
                        outs.append(new_gc)
                new_seg_cache = (jax.tree.map(lambda *a: jnp.stack(a), *outs)
                                 if seg_cache is not None and not paged
                                 else seg_cache)
            elif paged:
                (x, aux_total, new_seg_cache), _ = jax.lax.scan(
                    paged_group_fn, (x, aux_total, seg_cache),
                    (seg_params, jnp.arange(n)))
            elif seg_cache is not None:
                (x, aux_total), new_seg_cache = jax.lax.scan(
                    group_fn, (x, aux_total), (seg_params, seg_cache))
            else:
                (x, aux_total), _ = jax.lax.scan(
                    lambda c, gp: group_fn(c, (gp, None)), (x, aux_total),
                    seg_params)
                new_seg_cache = None
            new_caches.append(new_seg_cache)

    with jax.named_scope("final_norm"):
        x = norm_lib.apply_norm(cfg.norm, params["final_norm"], x)
    with jax.named_scope("lm_head"):
        logits = emb_lib.logits(params["embed"], x, softcap=cfg.logit_softcap)
    return logits, {"moe_aux": aux_total}, (new_caches if cache is not None
                                            else None)
