"""Dispatch-level roofline attribution for the serving engines.

The paper's co-optimization loop budgets every pipeline stage against the
hardware's peak rates; CirCNN's FPGA pipeline is costed stage-by-stage the
same way.  `repro.obs` (PR 6) gave the engines wall-clock spans and
`repro.roofline` gave the dry-run static cost cells — this module connects
them: every engine dispatch kind (per-bucket prefill, ``decode_chunk``)
carries the FLOP and byte counts of its *compiled executable*, captured
ONCE at compile time from its ``cost_analysis()``, and every fenced
dispatch then derives

    achieved FLOP/s   = flops / dt
    achieved bytes/s  = bytes_accessed / dt
    roofline fraction = bound_s / dt,   bound_s = max(flops / peak_FLOP/s,
                                                      bytes / HBM_bw)

against a ``roofline.HardwareSpec`` (host-CPU default, TPU presets).  A
fraction of 1.0 means the dispatch ran exactly at the spec's roofline for
its arithmetic intensity; serving dispatches on the host backend sit far
below it, and the *ratio between kinds* (prefill vs decode, bucket vs
bucket) is the attribution signal the one-dispatch-megakernel work needs.

Everything lands in the owning ``Obs`` registry —
``prof.flops_per_s{dispatch=...}`` / ``prof.bytes_per_s{dispatch=...}`` /
``prof.roofline_frac{dispatch=...}`` histograms — so ``stats()`` and the
JSONL emitter surface it with no extra plumbing.  The profiler also keeps
a bounded DISPATCH LOG of (kind, start, end) marks on the obs clock plus
per-dispatch samples of watched gauges (queue depth, free pages): the raw
material `obs/chrometrace.py` renders into Perfetto lanes and counter
tracks.

Cost: one ``cost_analysis()`` per compile (off the hot path), and per
dispatch three histogram observes + one deque append — skipped entirely
when ``Obs(enabled=False)``, so the paired ``obs_overhead`` budget
(<1 % tokens/s, BENCH_serving.json) still holds.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..roofline.analysis import (HARDWARE_PRESETS, HardwareSpec,
                                 detect_hardware)
from .metrics import Gauge, Registry, flat_name

# Log-spaced FLOP/s + bytes/s buckets covering host CPUs through TPU pods.
RATE_BUCKETS = tuple(float(10 ** e) for e in range(6, 16))     # 1e6..1e15
# Roofline fractions: log-spaced below 1.0 (host backends sit way down
# here), the overflow bucket catches >1.0 (spec pessimistic for the shape).
FRAC_BUCKETS = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.05, 0.1, 0.25, 0.5,
                0.75, 1.0)


@dataclasses.dataclass(frozen=True)
class DispatchCost:
    """Static cost of one compiled executable, captured at compile time.

    ``bound_s`` is the roofline-limited runtime on the profiler's
    ``HardwareSpec`` — the larger of the compute and memory terms — and
    ``bound`` names which side limits (ridge-point comparison)."""
    kind: str
    flops: float
    bytes_accessed: float
    t_compute_s: float
    t_memory_s: float

    @property
    def bound_s(self) -> float:
        return max(self.t_compute_s, self.t_memory_s)

    @property
    def bound(self) -> str:
        return ("compute" if self.t_compute_s >= self.t_memory_s
                else "memory")

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per HBM byte)."""
        return self.flops / max(self.bytes_accessed, 1.0)


class Profiler:
    """Per-dispatch roofline accounting into a ``repro.obs`` Registry.

    ``register(kind, compiled)`` runs once per compile and returns the
    ``DispatchCost`` handle the engine keeps next to the executable;
    ``on_dispatch(cost, t0, t1)`` runs once per fenced dispatch with marks
    on the obs clock.  Dispatch *kinds* are the attribution unit: the
    continuous engine registers ``prefill_{n}p`` per page bucket and one
    ``decode_chunk``; the batch engine tags its shapes
    (``prefill_b{B}_s{S}``, ``decode_loop_s{steps}_b{B}``).
    """

    def __init__(self, registry: Registry, *,
                 hardware: Optional[HardwareSpec] = None,
                 enabled: bool = True, keep_events: int = 100_000):
        self.registry = registry
        self.spec = hardware if hardware is not None else detect_hardware()
        self.enabled = bool(enabled)
        self.costs: Dict[str, DispatchCost] = {}
        # (kind, t_start_s, t_end_s, roofline_frac|None) on the obs clock —
        # bounded, FIFO; the Chrome-trace exporter's dispatch lanes
        self.events: deque = deque(maxlen=int(keep_events))
        # gauge samples taken at each dispatch end: name -> [(t_s, value)]
        self.samples: Dict[str, List[Tuple[float, float]]] = {}
        self._watched: List[Tuple[str, Gauge]] = []
        self._hists: Dict[str, Tuple] = {}

    # -- wiring (compile time / engine init) ------------------------------
    def register(self, kind: str, compiled) -> DispatchCost:
        """Capture a compiled executable's static cost under ``kind``.

        Re-registering a kind (the batch engine recompiles per shape)
        overwrites the static cost; the histograms accumulate across
        shapes of the kind.
        """
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
        cost = DispatchCost(
            kind=kind, flops=flops, bytes_accessed=nbytes,
            t_compute_s=flops / self.spec.peak_flops,
            t_memory_s=nbytes / self.spec.hbm_bw)
        self.costs[kind] = cost
        if kind not in self._hists:
            reg = self.registry
            self._hists[kind] = (
                reg.histogram("prof.flops_per_s", bounds=RATE_BUCKETS,
                              dispatch=kind),
                reg.histogram("prof.bytes_per_s", bounds=RATE_BUCKETS,
                              dispatch=kind),
                reg.histogram("prof.roofline_frac", bounds=FRAC_BUCKETS,
                              dispatch=kind),
            )
        return cost

    def watch(self, name: str, **labels) -> None:
        """Sample a registry gauge at every dispatch end (Chrome-trace
        counter tracks: queue depth, free pages, tokens in flight)."""
        if not self.enabled:
            return
        gauge = self.registry.gauge(name, **labels)
        key = flat_name(name, tuple(sorted(
            (k, str(v)) for k, v in labels.items())))
        if all(k != key for k, _ in self._watched):
            self._watched.append((key, gauge))
            self.samples.setdefault(key, [])

    # -- hot path (once per fenced dispatch) ------------------------------
    def on_dispatch(self, cost: DispatchCost, t0_s: float,
                    t1_s: float) -> None:
        """Record one fenced dispatch: ``t0_s``/``t1_s`` are obs-clock
        marks stamped around the device program (the engines fence with
        ``block_until_ready`` before ``t1``)."""
        if not self.enabled:
            return
        dt = max(t1_s - t0_s, 1e-9)
        h_flops, h_bytes, h_frac = self._hists[cost.kind]
        h_flops.observe(cost.flops / dt)
        h_bytes.observe(cost.bytes_accessed / dt)
        frac = cost.bound_s / dt
        h_frac.observe(frac)
        self.events.append((cost.kind, t0_s, t1_s, frac))
        for key, gauge in self._watched:
            self.samples[key].append((t1_s, gauge.value))

    # -- views ------------------------------------------------------------
    def summary(self) -> Dict[str, Dict]:
        """Per-dispatch-kind achieved rates for ``stats()``: static cost,
        dispatch count, mean/percentile achieved FLOP/s + bytes/s, and the
        roofline fraction against ``self.spec``."""
        out: Dict[str, Dict] = {}
        for kind, cost in sorted(self.costs.items()):
            h_flops, h_bytes, h_frac = self._hists[kind]
            n = h_frac.count
            out[kind] = {
                "dispatches": n,
                "flops": cost.flops,
                "bytes_accessed": cost.bytes_accessed,
                "intensity_flops_per_byte": cost.intensity,
                "bound": cost.bound,
                "bound_s": cost.bound_s,
                "achieved_flops_per_s": (h_flops.sum / n) if n else None,
                "achieved_bytes_per_s": (h_bytes.sum / n) if n else None,
                "roofline_frac": (h_frac.sum / n) if n else None,
                "roofline_frac_p50": h_frac.percentile(50),
                "roofline_frac_max": h_frac.max,
            }
        return out


class ScopedProfiler:
    """Label-scoped facade over a shared ``Profiler`` (``Obs.scoped``).

    A fleet of engines shares one profiler (one dispatch log, one Chrome
    trace) but each engine's view prefixes its dispatch *kinds*
    (``r0:decode_chunk``) and labels its watched gauges, so per-replica
    attribution falls out of the same machinery single-engine serving
    uses.  ``summary()`` filters to this scope's kinds — a replica's
    ``stats()['roofline']`` shows only its own dispatches.
    """

    def __init__(self, base: Profiler, labels: Dict[str, str]):
        self.base = base
        self.labels = {k: str(v) for k, v in labels.items()}
        self.prefix = ",".join(v for _, v in sorted(self.labels.items()))

    @property
    def spec(self) -> HardwareSpec:
        return self.base.spec

    @property
    def enabled(self) -> bool:
        return self.base.enabled

    @property
    def events(self):
        return self.base.events

    @property
    def costs(self):
        return self.base.costs

    @property
    def samples(self):
        return self.base.samples

    def _kind(self, kind: str) -> str:
        return f"{self.prefix}:{kind}" if self.prefix else kind

    def register(self, kind: str, compiled) -> DispatchCost:
        return self.base.register(self._kind(kind), compiled)

    def watch(self, name: str, **labels) -> None:
        merged = dict(self.labels)
        merged.update({k: str(v) for k, v in labels.items()})
        self.base.watch(name, **merged)

    def on_dispatch(self, cost: DispatchCost, t0_s: float,
                    t1_s: float) -> None:
        self.base.on_dispatch(cost, t0_s, t1_s)

    def summary(self) -> Dict[str, Dict]:
        if not self.prefix:
            return self.base.summary()
        pre = self.prefix + ":"
        return {k[len(pre):]: v for k, v in self.base.summary().items()
                if k.startswith(pre)}


# ---------------------------------------------------------------------------
# AOT capture: compile once, profile forever
# ---------------------------------------------------------------------------
def aot_compile(jitfn, args: Sequence, profiler: Profiler,
                kind: str) -> Tuple[Callable, DispatchCost]:
    """Lower + compile a ``jax.jit`` function for concrete ``args`` and
    register the executable's cost under ``kind``.

    The returned callable is the compiled executable itself — calling it is
    the same one-compile cost path ``jitfn(*args)`` would have taken, but
    the engine now holds the object whose ``cost_analysis()`` the profiler
    read (donation hints survive ``lower``).  A lowering or compile error
    raises: the program the device would run is the one that failed.
    """
    compiled = jitfn.lower(*args).compile()
    return compiled, profiler.register(kind, compiled)


def resolve_hardware(name: Optional[str]) -> HardwareSpec:
    """CLI helper: preset by name, ``None``/"auto" detects the backend."""
    if name is None or name == "auto":
        return detect_hardware()
    try:
        return HARDWARE_PRESETS[name]
    except KeyError:
        raise ValueError(f"unknown hardware preset {name!r}: expected one "
                         f"of {sorted(HARDWARE_PRESETS)} or 'auto'")
