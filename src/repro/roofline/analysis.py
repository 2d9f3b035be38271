"""Three-term roofline from the compiled dry-run artifact (no hardware).

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

``cost_analysis()`` on the SPMD-partitioned executable reports the LOCAL
(per-device) program, so terms are per-chip seconds directly.  Collective
bytes are NOT in cost_analysis: we parse the optimized HLO text and sum the
result-buffer sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (async -start counted once, -done skipped).
Caveats recorded in EXPERIMENTS.md: XLA "bytes accessed" counts every
operand/result touch (an upper bound on HBM traffic when fusions keep data
in VMEM); ring-collective wire bytes are ~(n-1)/n of buffer size, so the
collective term is likewise a slight upper bound.

MODEL_FLOPS uses the compression-aware convention: a dense projection costs
2·n_in·n_out per token, a block-circulant one costs its FFT-pipeline FLOPs
(the paper's O(n log n) accounting) — so the MODEL/HLO ratio measures how
much compiled compute is useful *relative to the compressed algorithm*, and
catches remat/replication waste rather than crediting compression twice.
MoE expert projections count top_k active experts per token.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, Tuple

import jax
import numpy as np

from ..configs.base import ArchConfig, ShapeSpec
from ..core import circulant as cc


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Peak rates of one device — the denominators of every roofline
    question.  The static dry-run cells and the live dispatch profiler
    (``repro.obs.prof``) both divide by these, so "fraction of roofline"
    means the same thing whether the cell was compiled dry or dispatched
    hot.  ``ridge_flops_per_byte`` is the arithmetic intensity at which a
    kernel stops being memory-bound on this part."""
    name: str
    peak_flops: float            # FLOP/s per chip
    hbm_bw: float                # HBM bytes/s per chip
    link_bw: float = 0.0         # bytes/s per interconnect link

    @property
    def ridge_flops_per_byte(self) -> float:
        return self.peak_flops / self.hbm_bw


# TPU v5e-class hardware constants (assignment-specified)
TPU_V5E = HardwareSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                       link_bw=50e9)
# TPU v4 (the dist rule engine's 256/512-chip mesh target)
TPU_V4 = HardwareSpec("tpu-v4", peak_flops=275e12, hbm_bw=1.2e12,
                      link_bw=50e9)
# One modern server-CPU socket, order of magnitude: tens of f32 GFLOP/s per
# core x a few dozen cores, ~50 GB/s effective DRAM stream.  Deliberately
# round numbers — on the host backend the profiler's roofline fraction is a
# sanity scale, not a calibrated claim (docs/observability.md).
HOST_CPU = HardwareSpec("host-cpu", peak_flops=2e11, hbm_bw=5e10)
# Generic data-center GPU placeholder until a real part is measured.
GPU_GENERIC = HardwareSpec("gpu-generic", peak_flops=1e14, hbm_bw=2e12,
                           link_bw=25e9)

HARDWARE_PRESETS = {s.name: s for s in (TPU_V5E, TPU_V4, HOST_CPU,
                                        GPU_GENERIC)}


# Accelerator presets keyed on ``Device.device_kind`` as JAX reports it.
DEVICE_KIND_PRESETS = {"TPU v5 lite": TPU_V5E, "TPU v4": TPU_V4}


def detect_hardware(device=None) -> HardwareSpec:
    """Preset for ``device`` (default: the first device).  The host preset
    covers platform ``cpu`` only; an accelerator whose ``device_kind`` has
    no preset raises rather than borrow another part's peaks."""
    device = device if device is not None else jax.devices()[0]
    if device.platform == "cpu":
        return HOST_CPU
    try:
        return DEVICE_KIND_PRESETS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware preset for {device.platform} device kind "
            f"{device.device_kind!r}: add its peaks to "
            f"DEVICE_KIND_PRESETS") from None


# Legacy module constants (EXPERIMENTS.md numbers were computed from these);
# the dry-run report still defaults to the TPU v5e spec.
PEAK_FLOPS = TPU_V5E.peak_flops      # bf16 FLOP/s per chip
HBM_BW = TPU_V5E.hbm_bw              # bytes/s per chip
LINK_BW = TPU_V5E.link_bw            # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z0-9-]+)")


def _shape_bytes(typestr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(typestr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes (per device) from optimized HLO."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        typestr, op = m.groups()
        base = op[:-6] if op.endswith("-start") else op
        if base.endswith("-done"):
            continue
        if base in out:
            out[base] += _shape_bytes(typestr)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# MODEL_FLOPS: compression-aware useful-work accounting
# ---------------------------------------------------------------------------
def model_flops_per_token(params_shapes: Any, cfg: ArchConfig) -> float:
    """Projection FLOPs per processed token (fwd only, 6N·D convention:
    attention score/AV FLOPs excluded, embedding gather excluded)."""
    topk = max(cfg.moe.top_k, 1)
    total = 0.0

    def one(path, leaf):
        nonlocal total
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        leaf_name = names[-1]
        is_expert = "experts" in names
        shape = leaf.shape
        if leaf_name == "table":                      # tied LM head matmul
            total += 2.0 * shape[0] * shape[1]
            return
        if leaf_name == "wc" or (is_expert and len(shape) >= 4 and
                                 leaf_name in ("up", "gate", "down")
                                 and shape[-1] <= 512):
            p_, q_, k_ = shape[-3], shape[-2], shape[-1]
            stack = math.prod(shape[:-3]) if len(shape) > 3 else 1
            if is_expert:                             # (stack, E, p, q, k)
                stack = stack // shape[-4] if len(shape) >= 4 else stack
                stack = math.prod(shape[:-4]) * topk
            flops = cc.bc_flops(1, q_ * k_, p_ * k_, k_)
            total += float(stack) * flops
            return
        if len(shape) >= 2 and leaf_name in (
                "w", "up", "gate", "down", "router", "wh", "ifg"):
            n_in, n_out = shape[-2], shape[-1]
            stack = math.prod(shape[:-2]) if len(shape) > 2 else 1
            if is_expert:                             # (stack, E, in, out)
                stack = (math.prod(shape[:-3]) if len(shape) > 3 else 1) * topk
            total += float(stack) * 2.0 * n_in * n_out

    jax.tree_util.tree_map_with_path(one, params_shapes)
    return total


def count_params(params_shapes: Any) -> int:
    return int(sum(math.prod(l.shape) for l in jax.tree.leaves(params_shapes)))


def seq_mixer_flops_per_token(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """Algorithmic FLOPs/token of the sequence mixers (attention scores+AV,
    recurrent state updates) — the PaLM-style MFU convention extended to the
    assigned families.  With 128x-compressed projections these dominate the
    useful work, so the MODEL/HLO ratio must include them."""
    from ..models.transformer import segments_for
    S = shape.seq_len
    a = cfg.attention
    hd = a.num_heads * a.head_dim

    def ctx(kind: str) -> float:
        w = a.sliding_window
        avg = S if shape.is_decode else S / 2          # causal average
        if kind in ("attn_local", "moe_swa") and w:
            return min(w, avg)
        return avg

    total = 0.0
    if cfg.is_encoder_decoder:
        # decoder self (causal) + cross to encoder_seq; encoder counted on
        # its own tokens (approximated onto decoder tokens by ratio).
        total += cfg.num_layers * 4.0 * hd * (S if shape.is_decode else S / 2)
        total += cfg.num_layers * 4.0 * hd * cfg.encoder_seq
        enc_tokens_ratio = (cfg.encoder_seq / max(S, 1)
                            if not shape.is_decode else cfg.encoder_seq)
        total += (cfg.encoder_layers * 4.0 * hd * cfg.encoder_seq *
                  (enc_tokens_ratio if shape.is_decode else
                   cfg.encoder_seq / max(S, 1)))
        return total
    for pattern, n in segments_for(cfg):
        for kind in pattern:
            if kind in ("attn", "attn_local", "moe", "moe_swa"):
                total += n * 4.0 * hd * ctx(kind)
            elif kind == "rec":
                total += n * 20.0 * (cfg.recurrent.lru_width or cfg.d_model)
            elif kind == "mlstm":
                d_in = int(cfg.d_model * cfg.recurrent.proj_factor)
                c = min(cfg.mlstm_chunk if not cfg.unroll_scan else 256, S)
                total += n * (2.0 * d_in * c + 8.0 * d_in *
                              (d_in // max(cfg.recurrent.mlstm_heads, 1)))
            elif kind == "slstm":
                total += n * (8.0 * cfg.d_model ** 2 + 64.0 * cfg.d_model)
    return total


def slstm_scan_correction(cfg: ArchConfig, shape: ShapeSpec,
                          dp_size: int) -> float:
    """Per-device FLOPs of the sLSTM time-recurrence beyond the once-costed
    scan body.  The strictly-sequential sLSTM scan cannot be unrolled at
    S=4k-500k, so its (S-1) extra body costs are added analytically:
    body = h@W_h matmul (2·b·d·4d) + ~16·4d·b gate elementwise per layer."""
    pattern = cfg.recurrent.pattern or ()
    if "slstm" not in pattern or shape.is_decode:
        return 0.0
    groups = cfg.num_layers // max(len(pattern), 1)
    n_slstm = sum(k == "slstm" for k in pattern) * groups
    b_local = max(shape.global_batch // dp_size, 1)
    d = cfg.d_model
    body = 2.0 * b_local * d * 4 * d + 16.0 * b_local * 4 * d
    factor = 3.0 if shape.kind == "train" else 1.0
    return n_slstm * (shape.seq_len - 1) * body * factor


# ---------------------------------------------------------------------------
def cell_report(lowered, compiled, cfg: ArchConfig, shape: ShapeSpec,
                mesh, spec: HardwareSpec = TPU_V5E) -> Dict:
    """All roofline quantities for one compiled cell (``spec`` picks the
    hardware denominators; the dry run keeps the TPU v5e default)."""
    chips = int(np.prod(mesh.devices.shape))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_size = sizes.get("pod", 1) * sizes.get("data", 1)
    ca = compiled.cost_analysis()
    slstm_extra = (slstm_scan_correction(cfg, shape, dp_size)
                   if cfg.unroll_scan else 0.0)
    flops = float(ca.get("flops", 0.0)) + slstm_extra
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        "code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
    }
    bytes_per_device = (mem["argument_bytes"] + mem["output_bytes"] +
                        mem["temp_bytes"] - mem["alias_bytes"])
    coll = collective_bytes(compiled.as_text())

    t_compute = flops / spec.peak_flops
    t_memory = bytes_acc / spec.hbm_bw
    t_coll = coll["total"] / (spec.link_bw or LINK_BW)
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    from ..models.registry import build_model
    model = build_model(cfg)
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    fwd_per_tok = (model_flops_per_token(params_shapes, cfg) +
                   seq_mixer_flops_per_token(cfg, shape))
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 3.0 * fwd_per_tok * tokens          # fwd + 2x bwd
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = fwd_per_tok * tokens
    else:
        tokens = shape.global_batch                        # one token per seq
        model_flops = fwd_per_tok * tokens

    hlo_global = flops * chips
    t_model = model_flops / chips / spec.peak_flops
    bound = max(terms.values())
    return {
        "hardware": spec.name,
        "chips": chips,
        "slstm_correction_flops": slstm_extra,
        "flops_per_device": flops,
        "bytes_accessed_per_device": bytes_acc,
        "bytes_per_device": bytes_per_device,
        "memory": mem,
        "collectives": coll,
        **{k: v for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": model_flops,
        "params": count_params(params_shapes),
        "model_hlo_ratio": model_flops / hlo_global if hlo_global else 0.0,
        "roofline_frac_overlap": t_model / bound if bound else 0.0,
        "roofline_frac_serial": (t_model / sum(terms.values())
                                 if sum(terms.values()) else 0.0),
    }
