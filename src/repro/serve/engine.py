"""Serving engines: the batch-synchronous engine (oracle) and the
continuous-batching engine over the paged KV pool.

``Engine`` gathers fixed-size batches (padding short prompts), prefills
once, then decodes with the device-resident loop in serve/decode.py.  It is
the bit-exact ORACLE: under a single-admission schedule (one request, B=1)
its greedy tokens define what the continuous engine must emit.  Prompt
bucketing (``bucket_prompts``) sorts requests by prompt length before
chunking into batches, so a chunk of short prompts is no longer left-padded
to an unrelated long prompt's length; results come back in request order.

``ContinuousEngine`` is the paper's batch-processing + resource-re-use +
hierarchical-control story as a serving control plane (see docs/serving.md):

* KV state lives in a PAGED POOL (serve/kvcache.py) — fixed-size blocks,
  per-request block tables, a free-list allocator; pages go back to the
  pool the moment a request retires, not when its batch drains;
* a request SCHEDULER (serve/scheduler.py) admits queued requests into
  free decode slots under a token budget, BETWEEN device dispatches of the
  scanned decode loop: prefill of waiting requests interleaves with decode
  of running ones;
* decode runs ``decode_chunk`` tokens per dispatch with per-slot positions
  (serve/decode.py: make_paged_decode_loop); finished slots freeze
  on-device and retire between dispatches without stalling the rest.

Params run through the offline spectral precompute pass (serve/params.py)
in both engines, so no weight FFT executes inside any serve program.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..dist import ctx as dist_ctx
from ..dist import sharding as dist_sharding
from ..launch import mesh as mesh_lib
from ..models import transformer as tfm
from ..models.registry import build_model
from ..obs import BYTES_BUCKETS, RATIO_BUCKETS, Obs, aot_compile
from ..obs.health import SCALE_BUCKETS, HealthPlane, ShadowOracle
from ..obs.scopes import program_scopes
from ..quant.codec import QuantPolicy, plane_clip_report
from . import decode as dec
from . import kvcache as kvc
from .params import precompute_serving_params
from .scheduler import (CANCELLED, FAILED, FINISHED_BUDGET, FINISHED_EOS,
                        REJECTED, TIMEOUT, Scheduler)

# Counters both engines keep in their obs registry under the SAME names and
# units — the unified stats() schema (docs/observability.md).  ``*_s``
# counters accumulate seconds; the rest are token/request counts.
ENGINE_COUNTERS = ("requests", "tokens", "prompt_tokens",
                   "padded_prompt_tokens", "prefill_s", "decode_s",
                   "dispatches")


def _engine_stats_view(obs: Obs, engine: str) -> Dict:
    """The shared half of Engine.stats()/ContinuousEngine.stats(): a view
    over the registry counters plus the derived fields both engines define
    identically (tokens_per_s over end-to-end serve time, pad waste)."""
    v = obs.registry.value
    st = {"engine": engine}
    for name in ENGINE_COUNTERS:
        val = v(name)
        st[name] = val if name.endswith("_s") else int(val)
    st["prompt_pad_waste"] = (st["padded_prompt_tokens"]
                              - st["prompt_tokens"])
    st["tokens_per_s"] = st["tokens"] / max(
        st["prefill_s"] + st["decode_s"], 1e-9)
    return st


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    id: int = 0
    # relative deadline (seconds after arrival; None = none).  Enforced by
    # the continuous engine both in-queue and in-flight — the batch engine
    # ignores it (its whole batch is one dispatch; see docs/serving.md).
    deadline_s: Optional[float] = None
    # shedding priority (repro.fleet): lower sheds first when the fleet is
    # saturated.  Engines ignore it — admission stays strictly FIFO.
    priority: int = 0


class Engine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256, sample: bool = False, mesh=None,
                 precompute: bool = True, decode_mode: str = "scan",
                 eos_id: Optional[int] = None, temperature: float = 1.0,
                 seed: int = 0, bucket_prompts: bool = True,
                 quant: Optional[QuantPolicy] = None,
                 obs: Optional[Obs] = None):
        assert decode_mode in ("scan", "per_token"), decode_mode
        self.cfg = cfg
        self.quant = quant or QuantPolicy()
        # the batch engine's dense cache stays float32 (it is the f32
        # parity ORACLE); only the weight half of the policy applies here
        self.params = (precompute_serving_params(params, cfg, self.quant)
                       if precompute else params)
        self.model = build_model(cfg)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.sample = sample
        self.decode_mode = decode_mode
        self.eos_id = eos_id
        self.temperature = temperature
        self.seed = seed
        self.bucket_prompts = bucket_prompts
        # Largest sliding window any block uses: the ring-buffer prefill
        # keeps the window tail, so batch prompts must cover it (validated
        # per batch below instead of failing as a trace-time assert).
        self._swa_window = 0 if cfg.is_encoder_decoder else max(
            [tfm._window_for(kind, cfg)
             for pattern, _ in tfm.segments_for(cfg)
             for kind in pattern], default=0)
        # Activations are pinned through the same policy the production
        # dry-run uses; default is this host's (n, 1) data-parallel mesh.
        self.mesh = mesh if mesh is not None else mesh_lib.make_host_mesh()
        self._prefill = jax.jit(dec.make_prefill_step(cfg))
        self._decode = jax.jit(
            dec.make_decode_step(cfg, sample=sample, temperature=temperature,
                                 seed=seed),
            donate_argnums=(2,))
        self._loops: Dict[int, object] = {}
        # AOT-compiled executables per concrete shape: (callable, cost).
        # Compiling via .lower().compile() instead of letting the jit
        # wrapper trace on first call costs nothing extra (one compile
        # either way) and hands the profiler the executable whose
        # cost_analysis() prices every later dispatch of that shape.
        self._aot: Dict[tuple, tuple] = {}
        # telemetry (repro.obs): the registry IS the stats() backing store;
        # counters are held directly so the hot path is one float add
        self.obs = obs if obs is not None else Obs()
        reg = self.obs.registry
        self._ctr = {n: reg.counter(n) for n in ENGINE_COUNTERS}
        self._h_prefill = reg.histogram("engine.prefill_dispatch_s")
        self._h_decode = reg.histogram("engine.decode_dispatch_s")
        self._order = 0                     # trace submission order

    def _loop_fn(self, steps: int):
        """jit'd decode loop for a step budget (cached per budget)."""
        fn = self._loops.get(steps)
        if fn is None:
            fn = jax.jit(dec.make_decode_loop(
                self.cfg, steps, sample=self.sample,
                temperature=self.temperature, eos_id=self.eos_id,
                seed=self.seed),
                donate_argnums=(2,))
            self._loops[steps] = fn
        return fn

    def _make_batch(self, reqs: Sequence[Request]) -> Dict:
        B = len(reqs)
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt     # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.frontend == "audio_stub":
            batch["frames"] = jnp.zeros(
                (B, self.cfg.encoder_seq, self.cfg.d_model), jnp.float32)
        elif self.cfg.frontend == "vision_stub":
            batch["patches"] = jnp.zeros(
                (B, self.cfg.num_patches, self.cfg.d_model), jnp.float32)
        return batch

    def generate(self, reqs: Sequence[Request]) -> List[Dict]:
        """Serve a batch of requests; returns per-request token lists in
        request order.  With ``bucket_prompts`` (default), requests are
        grouped into batches by (prompt length, decode budget) first, so a
        chunk of short prompts is not left-padded to an unrelated long
        prompt's length — and short decodes are not held hostage by a
        batch-mate's long budget (the decode loop runs to the chunk max)."""
        if self.bucket_prompts:
            order = sorted(range(len(reqs)),
                           key=lambda i: (len(reqs[i].prompt),
                                          reqs[i].max_new_tokens))
        else:
            order = list(range(len(reqs)))
        # every request enqueues NOW; later batches' traces carry the queue
        # wait their bucket imposed (admit - enqueue)
        t_enq = self.obs.now()
        traces = [None] * len(reqs)
        if self.obs.enabled:
            for i, r in enumerate(reqs):
                traces[i] = self.obs.trace_start(r.id, self._order,
                                                 len(r.prompt), t_enq)
                self._order += 1
        out: List[Optional[Dict]] = [None] * len(reqs)
        for i in range(0, len(order), self.max_batch):
            idxs = order[i:i + self.max_batch]
            batch_out = self._generate_batch([reqs[j] for j in idxs],
                                             [traces[j] for j in idxs])
            for j, r in zip(idxs, batch_out):
                out[j] = r
        return out

    def _generate_batch(self, reqs: Sequence[Request],
                        traces: Optional[Sequence] = None) -> List[Dict]:
        with dist_ctx.activation_policy(self.mesh):
            return self._generate_batch_inner(
                reqs, traces if traces is not None else [None] * len(reqs))

    def _generate_batch_inner(self, reqs: Sequence[Request],
                              traces: Sequence) -> List[Dict]:
        t0 = time.perf_counter()
        batch = self._make_batch(reqs)
        B, S = batch["tokens"].shape
        if S > self.max_seq:
            raise ValueError(f"prompt length {S} exceeds max_seq "
                             f"{self.max_seq}")
        # Decode step j writes cache position S+j-1 (j=1..steps-1), so the
        # cache needs S+steps-1 slots; clamp the step budget instead of
        # letting dynamic_update_slice silently clobber the last slot
        # (regression-tested in test_decode_loop.py).
        steps = max(r.max_new_tokens for r in reqs)
        steps = max(1, min(steps, self.max_seq - S + 1))
        need = min(self._swa_window, S + steps - 1)
        if self._swa_window and S < need:
            raise ValueError(
                f"batch prompt length {S} does not cover the sliding-window "
                f"ring buffer ({need}): SWA prefill keeps the window tail, "
                f"so prompts must be >= min(window, cache length)")
        cache = self.model.init_cache(B, S + steps - 1, dtype=jnp.float32)
        prof = self.obs.profiler
        key = ("prefill", B, S, steps)
        if key not in self._aot:
            self._aot[key] = aot_compile(
                self._prefill, (self.params, batch, cache), prof,
                dec.batch_prefill_kind(B, S))
        pf, pf_cost = self._aot[key]
        logits, cache = pf(self.params, batch, cache)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        # fence BEFORE every span boundary: the t1/t2 marks (and the trace
        # spans derived from them) measure device work, not dispatch
        jax.block_until_ready(nxt)
        t1 = time.perf_counter()
        loop_cost = None

        if self.decode_mode == "per_token":
            gen = self._decode_per_token(nxt, cache, S, steps)
        else:
            lengths = jnp.asarray([min(r.max_new_tokens, steps)
                                   for r in reqs], jnp.int32)
            lkey = ("loop", steps, B, S)
            if lkey not in self._aot:
                self._aot[lkey] = aot_compile(
                    self._loop_fn(steps),
                    (self.params, nxt, cache, jnp.int32(S), lengths),
                    prof, dec.batch_decode_kind(steps, B))
            loop, loop_cost = self._aot[lkey]
            gen, _ = loop(self.params, nxt, cache, jnp.int32(S), lengths)
        jax.block_until_ready(gen)
        gen = np.asarray(gen)                          # (B, steps)
        t2 = time.perf_counter()
        prefill_s, decode_s = t1 - t0, t2 - t1
        prof.on_dispatch(pf_cost, self.obs.rebase(t0), self.obs.rebase(t1))
        if self.decode_mode != "per_token":
            prof.on_dispatch(loop_cost, self.obs.rebase(t1),
                             self.obs.rebase(t2))

        out = []
        for i, r in enumerate(reqs):
            toks = gen[i, :min(r.max_new_tokens, steps)].tolist()
            if self.eos_id is not None and self.eos_id in toks:
                toks = toks[:toks.index(self.eos_id) + 1]
            status = (FINISHED_EOS if (self.eos_id is not None and toks
                                       and toks[-1] == self.eos_id)
                      else FINISHED_BUDGET)
            out.append({
                "id": r.id,
                "tokens": toks,
                "decode_len": len(toks),
                "status": status,
                "preemptions": 0,
                "tokens_per_s": len(toks) / max(decode_s, 1e-9),
                "prefill_s": prefill_s,
                "decode_s": decode_s,
                "latency_s": prefill_s + decode_s,
            })
        c = self._ctr
        c["requests"].inc(len(reqs))
        c["dispatches"].inc()
        c["tokens"].inc(sum(r["decode_len"] for r in out))
        c["prompt_tokens"].inc(sum(len(r.prompt) for r in reqs))
        c["padded_prompt_tokens"].inc(B * S)
        c["prefill_s"].inc(prefill_s)
        c["decode_s"].inc(decode_s)
        if self.obs.enabled:
            self._h_prefill.observe(prefill_s)
            self._h_decode.observe(decode_s)
            for tr, res in zip(traces, out):
                if tr is None:
                    continue
                tr.status = res["status"]
                tr.mark_admit(self.obs.rebase(t0))
                tr.mark_first_token(self.obs.rebase(t1))
                if res["decode_len"] > 1:
                    tr.mark_chunk(self.obs.rebase(t2),
                                  res["decode_len"] - 1)
                tr.mark_retire(self.obs.rebase(t2))
                self.obs.trace_finish(tr)
        self.obs.tick()
        return out

    def _decode_per_token(self, nxt, cache, S: int, steps: int) -> np.ndarray:
        """Seed host loop: one dispatch per token (baseline/oracle path)."""
        toks = [nxt]
        for pos in range(S, S + steps - 1):
            _, nxt, cache = self._decode(self.params, nxt[:, None], cache,
                                         jnp.int32(pos))
            toks.append(nxt)
        return np.asarray(jnp.stack(toks, 1))          # (B, steps)

    def stats(self) -> Dict:
        """Cumulative engine telemetry as a view over the obs registry —
        one schema shared with ContinuousEngine.stats()
        (docs/observability.md).  ``batches`` is the legacy alias for the
        unified ``dispatches`` counter (one decode dispatch per batch)."""
        st = _engine_stats_view(self.obs, "batch")
        st["batches"] = st["dispatches"]     # legacy alias (one release)
        st["hardware"] = self.obs.profiler.spec.name
        st["roofline"] = self.obs.profiler.summary()
        return st


# ---------------------------------------------------------------------------
# Continuous batching over the paged pool
# ---------------------------------------------------------------------------
class ContinuousEngine:
    """Continuous-batching engine: paged KV pool + token-budget scheduler.

    Serves decoder-LM archs with linear (global-attention) caches — see
    ``kvcache.servable_reasons``; SWA/recurrent/enc-dec archs stay on the
    batch engine.  Greedy outputs are token-identical to the batch engine
    run per-request (B=1): prefill is exact-position (right-pad bucketed),
    decode runs every slot at its own absolute position.

    ``generate(reqs, arrival_times=...)`` simulates an online arrival
    process against wall-clock time (benchmarks); without arrival times the
    whole list queues at t=0 and drains under the admission policy.

    Request lifecycle (docs/serving.md): every submitted request reaches
    exactly one terminal status.  ``admission="optimistic"`` (default)
    reserves only the prefill pages at admit and grows pages before each
    decode dispatch — on pool exhaustion the youngest running slot is
    PREEMPTED (pages freed, request re-queued for recompute-prefill with
    its generated tokens teacher-forced through the prompt), bounded by
    ``max_preemptions`` per request; greedy outputs stay token-identical
    to the oracle across preemption.  Deadlines (``Request.deadline_s``,
    relative to arrival) are enforced in-queue and in-flight (TIMEOUT);
    ``cancel(request_id)`` works in both places (CANCELLED); ``max_queue``
    bounds the submit queue (REJECTED backpressure); ``drain()`` stops
    intake, sheds fresh queued work, finishes in-flight requests, and
    flushes the obs emitter.  A ``faults`` injector (serve/faults.py)
    hooks allocator failures, dispatch delays, and slot corruption — the
    NaN/Inf guard (``nan_guard``) retires poisoned slots FAILED.
    """

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 4,
                 max_seq: int = 256, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_tokens_in_flight: Optional[int] = None,
                 decode_chunk: int = 8, sample: bool = False,
                 temperature: float = 1.0, seed: int = 0,
                 eos_id: Optional[int] = None, mesh=None,
                 precompute: bool = True, paged_attn: str = "stream",
                 quant: Optional[QuantPolicy] = None,
                 obs: Optional[Obs] = None,
                 admission: str = "optimistic",
                 max_queue: Optional[int] = None,
                 max_preemptions: int = 4,
                 nan_guard: bool = True,
                 faults=None,
                 shadow_sample: float = 0.0,
                 capture: Optional[bool] = None):
        if paged_attn not in ("stream", "gather"):
            raise ValueError(f"paged_attn {paged_attn!r}: "
                             f"expected 'stream' or 'gather'")
        reasons = kvc.servable_reasons(cfg)
        if reasons:
            raise ValueError(f"{cfg.name} is not continuous-servable: "
                             f"{'; '.join(reasons)} — use Engine")
        self.cfg = cfg
        self.quant = quant or QuantPolicy()
        # the engine's devices: one unless the caller hands it a wider
        # mesh; params replicate over it, the pool shards over it (below)
        self.mesh = (mesh if mesh is not None
                     else mesh_lib.make_device_mesh())
        raw_params = params                 # pre-precompute tree (shadow
        self.params = (precompute_serving_params(params, cfg, self.quant)
                       if precompute else params)  # oracle replays from it)
        self.params = jax.device_put(self.params, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()))
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.decode_chunk = decode_chunk
        self.sample = sample
        self.eos_id = eos_id
        self.paged_attn = paged_attn
        self.max_pages_per_slot = kvc.pages_for(max_seq, page_size)
        if num_pages is None:
            num_pages = max_slots * self.max_pages_per_slot + 1
        if num_pages < self.max_pages_per_slot + 1:
            raise ValueError(f"num_pages {num_pages} cannot hold one "
                             f"max_seq request (+trash page)")
        if max_tokens_in_flight is None:
            # Streamed paged attention (the default) never materializes the
            # (B, maxp*page, Hkv, D) gathered KV view, so peak decode memory
            # no longer scales with slots x max_seq — the default admission
            # budget fills every slot.  The gather oracle's default is NEWLY
            # halved here (PR 3 defaulted to the ceiling): every token it
            # has in flight pays an O(max_seq) gather per decode step, so
            # its memory-honest budget is conservative.  Pass
            # max_tokens_in_flight explicitly to A/B the attention paths
            # under identical admission.
            ceiling = max_slots * (max_seq + 1)
            max_tokens_in_flight = (ceiling if paged_attn == "stream"
                                    else max(max_seq + 1, ceiling // 2))
        if max_tokens_in_flight < max_seq + 1:
            raise ValueError(f"max_tokens_in_flight {max_tokens_in_flight} "
                             f"cannot admit one max_seq request")
        # keep the page dim DP-divisible, else page_pool_spec's fallback
        # would replicate the whole pool over the data-parallel devices
        num_pages = dist_sharding.dp_round_up(num_pages, self.mesh)
        self.num_pages = num_pages
        self.pool = kvc.build_pool(cfg, num_pages, page_size, self.quant)
        # pin the pool to its derived layout (pages over DP, heads over
        # "model" — the dense cache's placement, see dist/sharding.py);
        # trivial on a one-device mesh, load-bearing on wider ones
        self.pool = jax.device_put(self.pool, dist_sharding.to_shardings(
            dist_sharding.pool_specs(self.pool, self.mesh), self.mesh))
        # telemetry (repro.obs): the registry backs stats(); the allocator
        # and scheduler write their own gauges/counters into it
        self.obs = obs if obs is not None else Obs()
        reg = self.obs.registry
        # numerics capture rides obs.enabled: disabled obs compiles the
        # exact pre-health device programs (stats leaves are None pytree
        # leaves, not zero-filled buffers), so the obs_overhead bench's
        # disabled arm stays an honest baseline.  ``capture=False`` opts
        # an enabled-obs engine out of the health plane — the bench's
        # middle arm, which isolates the capture's incremental price from
        # the rest of the telemetry stack.
        self._capture = (self.obs.enabled if capture is None
                         else bool(capture) and self.obs.enabled)
        self.faults = faults
        self.block_table = kvc.BlockTable(
            kvc.PageAllocator(num_pages, registry=reg,
                              fault=(faults.alloc_fault
                                     if faults is not None else None)),
            max_slots, page_size, self.max_pages_per_slot)
        self.scheduler = Scheduler(self.block_table, max_seq=max_seq,
                                   max_tokens_in_flight=max_tokens_in_flight,
                                   registry=reg, admission=admission,
                                   max_queue=max_queue,
                                   max_preemptions=max_preemptions)
        # sample the control-plane gauges at every dispatch end — the
        # Chrome-trace counter tracks (obs/chrometrace.py)
        for gname in ("pool.free_pages", "sched.queue_depth",
                      "sched.tokens_in_flight"):
            self.obs.profiler.watch(gname)
        # ONE fixed-size decode program: chunk size never varies, so the
        # loop compiles exactly once — adaptive sizing would dodge some
        # frozen-slot steps but risks multi-second mid-serving compiles the
        # first time an unseen size comes up (disastrous for tail latency)
        self._loop = jax.jit(dec.make_paged_decode_loop(
            cfg, decode_chunk, sample=sample, temperature=temperature,
            eos_id=eos_id, seed=seed, paged_impl=paged_attn,
            nan_guard=nan_guard, capture_stats=self._capture),
            donate_argnums=(2,))
        # AOT executable + DispatchCost for the one decode program,
        # captured at the first dispatch (obs/prof.py); prefill buckets
        # cache theirs in self._prefills
        self._loop_exec = None
        self.nan_guard = nan_guard
        self._prefills: Dict[int, tuple] = {}
        self._scopes: Dict[str, Dict] = {}  # op_scopes(), by kind
        self._cur = np.zeros(max_slots, np.int32)
        self._pos = np.zeros(max_slots, np.int32)
        self._rem = np.zeros(max_slots, np.int32)
        self._dev_table = None              # device copy of the block table
        self._table_version = -1            # BlockTable.version it mirrors
        self._ctr = {n: reg.counter(n) for n in ENGINE_COUNTERS}
        self._c_anom = reg.counter("engine.anomalies")
        self._h_prefill = reg.histogram("engine.prefill_dispatch_s")
        self._h_chunk = reg.histogram("engine.decode_chunk_s")
        # the decode program's temporaries, from its executable: the pool
        # rides the program's carry in place, so this stays under one pool
        self._g_temp = reg.gauge("engine.decode_temp_bytes")
        # host time of a step in which the device had nothing queued: the
        # step's span less its fences (engine.*.fence), one value per step
        # that dispatched a decode chunk
        self._h_step_host = reg.histogram("engine.step_host_s")
        self._fence_s = 0.0                 # fence seconds in this step
        self._c_h2d = reg.counter("engine.host_transfers", dir="h2d")
        self._c_d2h = reg.counter("engine.host_transfers", dir="d2h")
        self._h_occup = reg.histogram("sched.slot_occupancy",
                                      bounds=RATIO_BUCKETS)
        self._h_attn_bytes = reg.histogram("attn.bytes_per_token",
                                           bounds=BYTES_BUCKETS)
        self._c_growths = reg.counter("quant.scale_growths")
        # per-position attention byte term for the live bytes/token series
        self._attn_per_pos = kvc.attention_bytes_per_position(
            self.pool)["per_pos"]
        # numerics health plane (obs/health.py): folds the fixed-shape
        # stats side-outputs the captured device programs return, so the
        # binary NaN guard above becomes the degenerate case of labelled
        # absmax/entropy/margin histograms + non-finite counters
        self._health = HealthPlane(reg) if self._capture else None
        # quant clip telemetry: saturation pressure, not overflow — with
        # absmax scaling the block max sits AT the rail by construction,
        # so plane_clip_rate/kv_clip_rate read as "fraction of values at
        # the quantization rail" (docs/quantization.md)
        self._c_kv_clip = reg.counter("quant.clip.kv_clipped")
        self._c_kv_total = reg.counter("quant.clip.kv_total")
        self._g_kv_clip = reg.gauge("quant.kv_clip_rate")
        if self._capture and self.quant.quant_weights:
            prep = plane_clip_report(self.params)
            reg.counter("quant.clip.plane_clipped").inc(prep["clipped"])
            reg.counter("quant.clip.plane_total").inc(prep["total"])
            reg.gauge("quant.plane_clip_rate").set(
                prep["clipped"] / max(prep["total"], 1))
        # host shadow of the int8 pool's k/v scales: decode-dispatch diffs
        # count page-scatter requantize-on-grow events (scales only GROW)
        # and feed the scale histograms + requant-error accounting
        self._scales_host = (kvc.pool_scale_map(self.pool)
                             if self._capture and self.quant.kv_quantized
                             else None)
        self._h_scale = {}
        # device->host reads of one pool_scale_map (one per scale leaf)
        self._scale_leaves = sum(
            getattr(path[-1], "key", None) in ("k_scale", "v_scale")
            for path, _ in jax.tree_util.tree_leaves_with_path(self.pool))
        if self._scales_host is not None:
            for k in ("k_scale", "v_scale"):
                self._h_scale[k] = reg.histogram("quant." + k,
                                                 bounds=SCALE_BUCKETS)
            self._h_grow = reg.histogram("quant.scale_grow_ratio",
                                         bounds=RATIO_BUCKETS)
            # running bound on requantize error: a grown page rescales its
            # resident int8 values; per element the round-off is at most
            # new_scale/2, accumulated here per grown (page, head) group
            self._c_requant = reg.counter("quant.requant_error_bound")
        # shadow-oracle sampling (obs/health.py): replay a fraction of
        # FINISHED requests through the f32 dense-cache oracle between
        # dispatches — online greedy_agreement/logit_drift on the same
        # teacher-forced harness quant/calibrate.py runs offline
        self._shadow = None
        if shadow_sample > 0.0:
            if not precompute:
                raise ValueError("shadow_sample needs precompute=True: the "
                                 "oracle precomputes f32 serving params "
                                 "from the raw tree")
            self._shadow = ShadowOracle(cfg, raw_params, policy=self.quant,
                                        registry=reg, sample=shadow_sample,
                                        seed=seed, page_size=page_size)
        self._traces: Dict[int, object] = {}     # submission order -> trace
        self._t0_perf = None                # serve-clock origin (perf)
        self._results: Dict[int, Dict] = {}      # order -> terminal result
        self._cancels: set = set()          # request ids pending cancel
        self._stall_streak = 0              # consecutive all-stalled rounds
        self._stall_limit = 3               # then FAIL the youngest stalled
        # birth snapshot: every counter above now exists at its true zero,
        # so SLO rate windows cover the whole serve — a guard trip before
        # the first emit_every tick still lands in a visible delta
        # (obs/slo.py rate rules skip the baseline-less first snapshot)
        self.obs.baseline()

    @contextlib.contextmanager
    def _on_mesh(self):
        """Trace under the engine's activation policy, and make host
        inputs (tokens, tables, positions) on the engine's first device
        rather than the process default."""
        with dist_ctx.activation_policy(self.mesh), \
                jax.default_device(self.mesh.devices.flat[0]):
            yield

    # -- jit caches -------------------------------------------------------
    def _prefill_exec(self, n_pages: int, args) -> tuple:
        """(compiled callable, DispatchCost|None) for a page bucket —
        compiled AOT on first use with the bucket's concrete ``args`` so
        the profiler prices every later dispatch of the bucket."""
        ent = self._prefills.get(n_pages)
        if ent is None:
            jitfn = jax.jit(dec.make_prefill_pack_step(
                self.cfg, n_pages, self.page_size,
                capture_stats=self._capture), donate_argnums=(2,))
            with self.obs.span("engine.compile"):
                ent = aot_compile(jitfn, args, self.obs.profiler,
                                  dec.prefill_kind(n_pages))
            self._prefills[n_pages] = ent
        return ent

    def op_scopes(self) -> Dict[str, Dict]:
        """The op->scope map of every program compiled so far, by dispatch
        kind (``decode_chunk``, ``prefill_{n}p``): HLO instruction name ->
        its ``metadata op_name`` path, parsed from the executables this
        engine holds (repro.obs.scopes).  Built when asked and kept, off
        the serving path; read after a traced window to put the trace's
        device ops under their layer kinds."""
        progs = {dec.prefill_kind(n): ent[0]
                 for n, ent in self._prefills.items()}
        if self._loop_exec is not None:
            progs[dec.DECODE_CHUNK_KIND] = self._loop_exec[0]
        for kind, compiled in progs.items():
            if kind not in self._scopes:
                self._scopes[kind] = program_scopes(compiled)
        return dict(self._scopes)

    # -- public lifecycle API ---------------------------------------------
    def _now(self) -> float:
        """Seconds on the serve clock (0 at the first submit)."""
        if self._t0_perf is None:
            self._t0_perf = time.perf_counter()
        return time.perf_counter() - self._t0_perf

    def reset_serve_clock(self) -> None:
        """Re-anchor the serve clock at the next submit/step.  A fleet
        replica calls this when adopting a (possibly warmed) engine:
        arrival and deadline stamps are router-relative, and an engine
        whose clock still counts from a warmup ``generate`` would see
        every stamp seconds in the past and expire fresh deadlines on
        arrival.  Only legal while idle — in-flight work carries absolute
        stamps on the current clock."""
        if not self.scheduler.idle:
            raise RuntimeError("reset_serve_clock with work in flight")
        self._t0_perf = None

    def submit(self, request: Request, arrival_s: float = 0.0, *,
               resume_tokens: Optional[Sequence[int]] = None,
               preemptions: int = 0) -> int:
        """Queue one request; returns its order (the key for results).

        A rejected submission (queue bound hit / draining) still gets an
        order and an immediate REJECTED terminal result — callers never
        lose a request.

        ``resume_tokens`` re-enters a request mid-stream (cross-replica
        failover migration, repro.fleet): the tokens are teacher-forced
        through recompute-prefill exactly like a local preemption's
        resume, so greedy decode stays token-identical to the B=1 oracle.
        ``preemptions`` carries the request's eviction count across the
        migration for honest end-to-end accounting."""
        if len(request.prompt) > self.max_seq:
            raise ValueError(f"prompt length {len(request.prompt)} exceeds "
                             f"max_seq {self.max_seq}")
        resume = list(resume_tokens) if resume_tokens else []
        if len(request.prompt) + len(resume) > self.max_seq:
            raise ValueError(
                f"prompt + resume length {len(request.prompt) + len(resume)} "
                f"exceeds max_seq {self.max_seq}")
        self._now()                          # pin the serve clock
        order, accepted = self.scheduler.submit(request, arrival_s,
                                                resume_tokens=resume,
                                                preemptions=preemptions)
        if self.obs.enabled:
            # a request ENQUEUES at its (possibly simulated) arrival — the
            # trace timeline starts there so queue_s covers admission wait
            self._traces[order] = self.obs.trace_start(
                request.id, order, len(request.prompt),
                self.obs.rebase(self._t0_perf) + arrival_s)
        if not accepted:
            self._finish_unserved(order, request, resume, REJECTED,
                                  preemptions=preemptions)
        return order

    def cancel(self, request_id) -> bool:
        """Cancel a request wherever it lives.  Queued: the CANCELLED
        result materializes immediately.  Running: the slot is retired at
        the next step boundary (its in-flight chunk is abandoned).
        Returns False when the id is unknown or already terminal."""
        found = self.scheduler.cancel(request_id)
        if found is None:
            return False
        kind, obj = found
        if kind == "queued":
            self._finish_unserved(obj.order, obj.request, obj.resume_tokens,
                                  CANCELLED, preemptions=obj.preemptions)
        else:
            self._cancels.add(request_id)
        return True

    def step(self) -> bool:
        """Run one scheduler round: expire deadlines, apply cancels, admit
        + prefill, grow pages (possibly preempting), dispatch one decode
        chunk, retire finished slots.  Admission honors submit-time
        arrival stamps (a request whose simulated arrival is still in the
        future stays queued).  Returns True if anything happened — the
        low-level API the chaos harness drives; ``generate`` is a loop
        over this."""
        with self._on_mesh():
            now = self._now()
            return self._step(now, arrived_before=now)

    def drain(self) -> List[Dict]:
        """Graceful shutdown: stop admitting, shed fresh queued work as
        REJECTED, run in-flight requests (including preempted ones) to
        their terminal state, flush + close the obs emitter.  Returns the
        results of everything that went terminal during the drain."""
        before = set(self._results)
        self.scheduler.close_intake()
        for entry in self.scheduler.flush_queue():
            self._finish_unserved(entry.order, entry.request,
                                  entry.resume_tokens, REJECTED,
                                  preemptions=entry.preemptions)
        with self._on_mesh():
            while not self.scheduler.idle:
                if not self._step(self._now()):
                    raise RuntimeError("drain stall: in-flight work cannot "
                                       "make progress")
            if self._shadow is not None:
                self._shadow.drain()
        self.obs.close()
        return [self._results[o] for o in sorted(set(self._results) - before)]

    def result(self, order: int, pop: bool = False) -> Optional[Dict]:
        """Terminal result for a submission order (None while in flight)."""
        return (self._results.pop(order, None) if pop
                else self._results.get(order))

    @property
    def anomalies(self) -> int:
        """Cumulative NaN/Inf-guard trips — the health signal
        ``fleet.EngineReplica`` folds into its DEGRADED transitions."""
        return int(self._c_anom.value)

    @property
    def programs_compiled(self) -> int:
        """Device programs compiled so far (one per prefill bucket, plus
        the decode loop): ``fleet.EngineReplica`` does not time a step in
        which this grew."""
        return len(self._prefills) + (self._loop_exec is not None)

    # -- serving loop -----------------------------------------------------
    def generate(self, reqs: Sequence[Request],
                 arrival_times: Optional[Sequence[float]] = None
                 ) -> List[Dict]:
        for r in reqs:                      # validate BEFORE admitting any:
            if len(r.prompt) > self.max_seq:   # a mid-loop raise would leak
                raise ValueError(              # running slots' pages
                    f"prompt length {len(r.prompt)} exceeds max_seq "
                    f"{self.max_seq}")
        self._t0_perf = time.perf_counter()
        arr = ([0.0] * len(reqs) if arrival_times is None
               else [float(a) for a in arrival_times])
        orders = [self.submit(r, a) for r, a in zip(reqs, arr)]
        gate = arrival_times is not None
        with self._on_mesh():
            while not self.scheduler.idle:
                now = self._now()
                if gate and not self.scheduler.running and \
                        self.scheduler.queue:
                    # engine idle: sleep until the HEAD's arrival (admission
                    # is strictly FIFO, so the head's arrival is the binding
                    # one even when arrival times are unsorted)
                    next_arr = self.scheduler.queue[0].arrival_s
                    if next_arr > now:
                        time.sleep(next_arr - now)
                        now = self._now()
                progress = self._step(now,
                                      arrived_before=now if gate else None)
                if (not progress and not self.scheduler.running
                        and self.scheduler.queue):
                    if (gate and
                            self.scheduler.queue[0].arrival_s > self._now()):
                        continue            # head simply hasn't arrived yet
                    raise RuntimeError(
                        "scheduler stall: queued request cannot be admitted "
                        "into an idle engine (budget/pool too small)")
            if self._shadow is not None:
                # flush pending replays so short runs still publish
                # agreement/drift before the caller reads stats()
                self._shadow.drain()
        return [self._results.pop(o) for o in orders]

    def _step(self, now_s: float,
              arrived_before: Optional[float] = None) -> bool:
        """One scheduler round between device dispatches, in the host span
        ``engine.step``; with a decode chunk dispatched, its host time
        less the fences is one ``engine.step_host_s`` value."""
        t0 = time.perf_counter()
        self._fence_s = 0.0
        with self.obs.span("engine.step"):
            progress, decoded = self._step_phases(now_s, arrived_before)
        if decoded and self.obs.enabled:
            self._h_step_host.observe(time.perf_counter() - t0
                                      - self._fence_s)
        return progress

    def _fence(self, name: str, outs) -> None:
        """``block_until_ready`` in the span ``name``, its seconds kept
        out of the step's host time."""
        t0 = time.perf_counter()
        with self.obs.span(name):
            jax.block_until_ready(outs)
        self._fence_s += time.perf_counter() - t0

    def _step_phases(self, now_s: float, arrived_before: Optional[float]):
        """(progress, whether a decode chunk was dispatched)."""
        sched = self.scheduler
        progress = False
        with self.obs.span("sched.admit"):
            # 1. queued deadlines
            for entry in sched.expire_queue(now_s):
                self._finish_unserved(entry.order, entry.request,
                                      entry.resume_tokens, TIMEOUT,
                                      preemptions=entry.preemptions)
                progress = True
            # 2. pending cancels of running slots (queued cancels resolved
            #    inside cancel(); stale ids — already terminal — dropped)
            if self._cancels:
                for slot in list(sched.running):
                    if slot.request.id in self._cancels:
                        self._finish(slot, CANCELLED)
                        progress = True
                self._cancels.clear()
            # 3. in-flight deadlines
            for slot in list(sched.running):
                if slot.deadline_s is not None and now_s > slot.deadline_s:
                    self._finish(slot, TIMEOUT)
                    progress = True
            # 4. admission (recompute-prefill for preempted entries)
            admitted = sched.try_admit(now_s, arrived_before)
            for entry in sched.drain_doomed():   # can NEVER fit the pool
                self._finish_unserved(entry.order, entry.request,
                                      entry.resume_tokens, FAILED,
                                      preemptions=entry.preemptions)
                progress = True
        for slot in admitted:
            self._prefill_slot(slot)
            progress = True
        # 5. page growth for the next chunk; preemptions free their victim's
        #    device state
        with self.obs.span("sched.grow"):
            prep = sched.prepare_decode(self.decode_chunk)
            t_pre = self.obs.rebase(time.perf_counter())
            for idx, entry in prep.preempted:
                self._rem[idx] = 0          # victim's slot is dead on device
                progress = True
                if self.obs.enabled:
                    tr = self._traces.get(entry.order)
                    if tr is not None:
                        tr.mark_preempt(t_pre, len(entry.resume_tokens))
        # 6. decode dispatch over the slots whose pages cover the chunk
        if admitted or prep.preempted or prep.runnable:
            self._stall_streak = 0
        if prep.runnable:
            self._dispatch_decode(prep.runnable, prep.stalled)
            progress = True
        elif prep.stalled:
            # every live slot is starved and no victim remains under the
            # preemption bound.  Transient allocator faults clear on retry,
            # so retry a bounded number of rounds; past the limit this is
            # genuine starvation — FAIL the youngest stalled slot to free
            # pages instead of livelocking.
            self._stall_streak += 1
            progress = True
            if self._stall_streak >= self._stall_limit:
                victim = max(prep.stalled, key=lambda s: s.order)
                self._finish(victim, FAILED)
                self._stall_streak = 0
        if self._shadow is not None:
            self._shadow.tick()     # at most one replay, off the hot path
        with self.obs.span("obs.tick"):
            self.obs.tick()         # emitter rides the dispatch cadence
        return progress, bool(prep.runnable)

    def _prefill_slot(self, slot) -> None:
        with self.obs.span("engine.prefill", order=slot.order):
            self._prefill_slot_spans(slot)

    def _prefill_slot_spans(self, slot) -> None:
        t0 = time.perf_counter()
        with self.obs.span("engine.prefill.launch"):
            req = slot.request
            # a resumed (preempted) request teacher-forces prompt +
            # generated tokens through prefill: greedy decode then
            # continues identically
            prompt = (list(np.asarray(req.prompt).tolist())
                      + list(slot.tokens))
            S = len(prompt)
            n_pages = kvc.pages_for(S, self.page_size)
            spad = n_pages * self.page_size
            toks = np.zeros(spad, np.int32)
            toks[:S] = prompt                          # right-pad
            batch = {"tokens": jnp.asarray(toks[None])}
            if self.cfg.frontend == "vision_stub":
                batch["patches"] = jnp.zeros(
                    (1, self.cfg.num_patches, self.cfg.d_model), jnp.float32)
            pages = jnp.asarray(
                self.block_table.pages(slot.index)[:n_pages], jnp.int32)
            true_len = jnp.int32(S)
            self._c_h2d.inc(3)                 # tokens, pages, true_len
            args = (self.params, batch, self.pool, pages, true_len)
            fn, cost = self._prefill_exec(n_pages, args)
            nxt, ok, self.pool, pstats = fn(*args)
        # fence the whole dispatch (token, page scatter AND the numerics
        # side-output) so the prefill span — and the trace's first-token
        # mark — measure device work, not a later host sync
        self._fence("engine.prefill.fence",
                    (nxt, self.pool) if pstats is None
                    else (nxt, self.pool, pstats))
        t1 = time.perf_counter()
        with self.obs.span("engine.prefill.fetch"):
            # the device packs the health side-output into ONE flat vector
            # [logit(4) | kv_clipped | kv_total | act_absmax...]: a single
            # device->host transfer per prefill, not four
            arr = (np.asarray(pstats, dtype=np.float64)
                   if self._health is not None and pstats is not None
                   else None)
            ok_h = bool(ok) if self.nan_guard else True
            first = int(nxt)
            self._c_d2h.inc(1 + self.nan_guard + (arr is not None))
        self.obs.profiler.on_dispatch(cost, self.obs.rebase(t0),
                                      self.obs.rebase(t1))
        dt = t1 - t0
        self._ctr["prefill_s"].inc(dt)
        self._ctr["prompt_tokens"].inc(S)
        self._ctr["padded_prompt_tokens"].inc(spad)
        slot.prefill_s = dt
        if arr is not None:
            # fold BEFORE the guard branch: a poisoned prefill must bump
            # health.nonfinite_* in the same dispatch the guard retires it
            with self.obs.span("health.fold"):
                self._health.on_prefill({"logit": arr[:4],
                                         "act_absmax": arr[6:]})
                kv_total = float(arr[5])
                if kv_total > 0:
                    self._c_kv_clip.inc(float(arr[4]))
                    self._c_kv_total.inc(kv_total)
                    self._g_kv_clip.set(self._c_kv_clip.value
                                        / max(self._c_kv_total.value, 1.0))
        if not ok_h:
            # poisoned prefill: never stream a garbage first token
            self._c_anom.inc()
            self._rem[slot.index] = 0
            if self.obs.enabled:
                self._h_prefill.observe(dt)
                tr = self._traces.get(slot.order)
                if tr is not None and tr.admit_s is None:
                    tr.mark_admit(self.obs.rebase(self._t0_perf)
                                  + slot.admit_s)
            self._finish(slot, FAILED)
            return
        slot.tokens.append(first)
        slot.pos = S                       # position of the token in flight
        slot.budget -= 1
        self._cur[slot.index] = first
        self._pos[slot.index] = S
        self._rem[slot.index] = slot.budget
        self._ctr["tokens"].inc()          # the prefill-emitted token
        if self.obs.enabled:
            self._h_prefill.observe(dt)
            tr = self._traces.get(slot.order)
            if tr is not None:
                t_first = self.obs.rebase(t1)
                if tr.admit_s is None:     # first admission of this request
                    tr.mark_admit(self.obs.rebase(self._t0_perf)
                                  + slot.admit_s)
                    tr.mark_first_token(t_first)
                else:                      # recompute-prefill after preempt
                    tr.mark_chunk(t_first, 1)
            if self._scales_host is not None:
                # prefill packs fresh pages (new scales, not grow events):
                # refresh the shadow so the next decode diff is clean, and
                # census the freshly written scales into the saturation
                # histograms
                new = kvc.pool_scale_map(self.pool)
                self._c_d2h.inc(self._scale_leaves)
                for k, h in self._h_scale.items():
                    fresh = new[k][(new[k] != self._scales_host[k])
                                   & (new[k] > 0)]
                    for sc in fresh.tolist():
                        h.observe(float(sc))
                self._scales_host = new
        if (len(slot.tokens) >= slot.total_budget
                or (self.eos_id is not None and first == self.eos_id)):
            self._rem[slot.index] = 0
            self._finish(slot)
        elif slot.deadline_s is not None and self._now() > slot.deadline_s:
            self._rem[slot.index] = 0
            self._finish(slot, TIMEOUT)

    def _dispatch_decode(self, runnable, stalled) -> None:
        if self.faults is not None:
            delay = self.faults.dispatch_delay()
            if delay > 0.0:
                time.sleep(delay)          # injected control-plane hiccup
            victim = self.faults.pick_corruption(runnable)
            if victim is not None:
                from .faults import poison_slot_pages
                self.pool = poison_slot_pages(
                    self.pool, self.block_table.pages(victim.index)[0])
        t0 = time.perf_counter()
        with self.obs.span("engine.decode.launch"):
            # stalled slots (no pages for the next chunk) are masked out of
            # this dispatch: rem=0 freezes them on device, their budget is
            # restored afterwards so they retry next round
            rem_dispatch = self._rem.copy()
            for s in stalled:
                rem_dispatch[s.index] = 0
            if self._table_version != self.block_table.version:
                self._dev_table = self.block_table.device_table()
                self._table_version = self.block_table.version
                self._c_h2d.inc()
            args = (self.params, jnp.asarray(self._cur), self.pool,
                    self._dev_table, jnp.asarray(self._pos),
                    jnp.asarray(rem_dispatch))
            self._c_h2d.inc(3)                 # cur, pos, rem
            if self._loop_exec is None:
                with self.obs.span("engine.compile"):
                    self._loop_exec = aot_compile(
                        self._loop, args, self.obs.profiler,
                        dec.DECODE_CHUNK_KIND)
                    self._g_temp.set(self._loop_exec[0].memory_analysis()
                                     .temp_size_in_bytes)
            loop, loop_cost = self._loop_exec
            buf, cur, self.pool, pos, rem, done, anom, dstats = loop(*args)
        # fence before the span boundary: the decode_chunk wall time (and
        # the per-chunk trace marks) measure the device program — the
        # numerics side-output fences with it, so the health fold below
        # is a pure host read
        self._fence("engine.decode.fence",
                    buf if dstats is None else (buf, dstats))
        t1 = time.perf_counter()
        with self.obs.span("engine.decode.fetch"):
            buf = np.asarray(buf)
            self._cur = np.array(cur)
            self._pos = np.array(pos)
            rem_after = np.array(rem)
            done = np.asarray(done)
            anom = np.asarray(anom)
            self._c_d2h.inc(6)
        with self.obs.span("engine.decode.emit"):
            self.obs.profiler.on_dispatch(loop_cost, self.obs.rebase(t0),
                                          self.obs.rebase(t1))
            saved = {s.index: self._rem[s.index] for s in stalled}
            self._rem = rem_after
            for idx, v in saved.items():
                self._rem[idx] = v
            dt = t1 - t0
            self._ctr["decode_s"].inc(dt)
            self._ctr["dispatches"].inc()
            if self.obs.enabled:
                self._h_chunk.observe(dt)
                self._h_occup.observe(len(runnable) / max(self.max_slots, 1))
                if self._health is not None and dstats is not None:
                    # steps[b] = tokens slot b advanced this dispatch:
                    # rows with 0 still carry init sentinels (or stale
                    # maxima from the donated carry); the fold skips them
                    with self.obs.span("health.fold"):
                        self._c_d2h.inc()
                        self._health.on_decode(np.asarray(dstats),
                                               steps=rem_dispatch - rem_after)
                if self._scales_host is not None:
                    new = kvc.pool_scale_map(self.pool)
                    self._c_d2h.inc(self._scale_leaves)
                    grown = 0
                    for k, old in self._scales_host.items():
                        g = new[k] > old
                        if g.any():
                            grown += int(g.sum())
                            ns, olds = new[k][g], old[g]
                            # per-element round-off of a rescale is bounded
                            # by new_scale/2; accumulate the per-group bound
                            self._c_requant.inc(float(0.5 * ns.sum()))
                            for s_old, s_new in zip(olds.tolist(),
                                                    ns.tolist()):
                                if s_new > 0:
                                    self._h_grow.observe(s_old / s_new)
                                self._h_scale[k].observe(s_new)
                    self._c_growths.inc(grown)
                    self._scales_host = new
            t_chunk = self.obs.rebase(t1)
            for slot in runnable:
                b = slot.index
                n = int(rem_dispatch[b] - rem_after[b])
                if n:
                    slot.tokens.extend(buf[b, :n].tolist())
                    slot.pos = int(self._pos[b])
                    self._ctr["tokens"].inc(n)
                    if self.obs.enabled:
                        # live-length bytes/token: what attention actually
                        # streamed for this slot (worst case is in stats())
                        self._h_attn_bytes.observe(
                            self._attn_per_pos * int(self._pos[b]))
                        tr = self._traces.get(slot.order)
                        if tr is not None:
                            tr.mark_chunk(t_chunk, n)
                if anom[b]:
                    self._c_anom.inc()
                    self._finish(slot, FAILED)
                elif done[b]:
                    self._finish(slot)

    # -- terminal transitions ---------------------------------------------
    def _finish(self, slot, status: Optional[str] = None) -> None:
        """Retire a slot-resident request.  ``status`` None infers the
        natural finish (EOS vs budget); explicit statuses come from the
        cancel/timeout/failure paths."""
        with self.obs.span("sched.retire"):
            self._retire(slot, status)

    def _retire(self, slot, status: Optional[str]) -> None:
        if status is None:
            toks = slot.tokens
            status = (FINISHED_EOS
                      if (self.eos_id is not None and toks
                          and toks[-1] == self.eos_id)
                      else FINISHED_BUDGET)
        if (self._shadow is not None
                and status in (FINISHED_EOS, FINISHED_BUDGET)):
            # only cleanly finished requests are parity-replayable (their
            # full greedy trajectory exists); the replay itself happens
            # between dispatches, in _step / drain
            self._shadow.maybe_enqueue(np.asarray(slot.request.prompt),
                                       len(slot.tokens))
        now = self._now()
        prefill_s = getattr(slot, "prefill_s", 0.0)
        arrival, admit = slot.arrival_s, slot.admit_s
        order = slot.order
        self._rem[slot.index] = 0           # device slot is dead
        res = self.scheduler.retire(slot, status)  # releases the pages
        tr = self._traces.pop(order, None)
        if tr is not None:
            # one timeline: the result's latency fields come FROM the trace,
            # so bench percentiles over results and over traces are the same
            # numbers by construction
            tr.status = status
            # clamp: a cancel/timeout can land before a SIMULATED arrival
            tr.mark_retire(max(self.obs.rebase(self._t0_perf) + now,
                               tr.enqueue_s))
            self.obs.trace_finish(tr)
            decode_s = tr.decode_s if tr.decode_s is not None else 0.0
            res.update({
                "tokens_per_s": res["decode_len"] / max(decode_s, 1e-9),
                "prefill_s": tr.prefill_s,
                "decode_s": decode_s,
                "queue_s": tr.queue_s,
                "latency_s": tr.latency_s,
            })
        else:
            decode_s = max(now - admit - prefill_s, 0.0)
            res.update({
                "tokens_per_s": res["decode_len"] / max(decode_s, 1e-9),
                "prefill_s": prefill_s,
                "decode_s": decode_s,
                "queue_s": max(admit - arrival, 0.0),
                "latency_s": max(now - arrival, 0.0),
            })
        self._ctr["requests"].inc()
        self._results[res.pop("order")] = res

    def _finish_unserved(self, order: int, request, tokens, status: str,
                         preemptions: int = 0) -> None:
        """Terminal result for a request that never (re)entered a slot —
        rejected, cancelled in queue, or expired in queue.  The scheduler
        already bumped the terminal counter on all of these paths."""
        now = self._now()
        tr = self._traces.pop(order, None)
        res = {
            "id": request.id,
            "tokens": list(tokens),
            "decode_len": len(tokens),
            "status": status,
            "preemptions": preemptions,
            "tokens_per_s": 0.0,
            "prefill_s": None,
            "decode_s": 0.0,
            "queue_s": None,
            "latency_s": None,
        }
        if tr is not None:
            tr.status = status
            # clamp: a cancel/reject can land before a SIMULATED arrival
            tr.mark_retire(max(self.obs.rebase(self._t0_perf) + now,
                               tr.enqueue_s))
            self.obs.trace_finish(tr)
            res["latency_s"] = tr.latency_s
            res["queue_s"] = tr.latency_s   # never admitted: all queue wait
        self._results[order] = res

    # -- telemetry --------------------------------------------------------
    def stats(self) -> Dict:
        """Engine + scheduler telemetry as a view over the obs registry —
        one schema shared with Engine.stats() (docs/observability.md):
        queue depth, in-flight tokens, page-pool utilization,
        prefill/decode split, pool footprint, and the decode-attention
        memory estimates (worst case: every slot at full length) the
        serving benchmarks record.  ``decode_dispatches`` is the legacy
        alias for the unified ``dispatches`` counter."""
        st = _engine_stats_view(self.obs, "continuous")
        st["decode_dispatches"] = st["dispatches"]  # legacy alias
        st.update(self.scheduler.stats())
        v = self.obs.registry.value
        st["anomalies"] = int(v("engine.anomalies"))
        st["free_pages"] = int(v("pool.free_pages"))
        # pool-pressure headroom: the low-water mark of the free list over
        # the whole serve (the number the prefix-cache sizing will need)
        low = self.obs.registry.gauge("pool.free_pages").min_seen
        st["min_free_pages"] = (int(low) if low is not None
                                else st["free_pages"])
        st["pages_alloc"] = int(v("pool.pages_alloc"))
        st["pages_freed"] = int(v("pool.pages_freed"))
        st["scale_growths"] = int(v("quant.scale_growths"))
        kv_total = v("quant.clip.kv_total")
        st["kv_clip_rate"] = (v("quant.clip.kv_clipped") / kv_total
                              if kv_total else None)
        if self._health is not None:
            st["health"] = self._health.stats()
        if self._shadow is not None:
            st["shadow_oracle"] = self._shadow.stats()
        st["pool_bytes"] = kvc.pool_bytes(self.pool)
        st["kv_pool_bytes"] = st["pool_bytes"]     # quant-satellite alias
        st["decode_temp_bytes"] = (int(v("engine.decode_temp_bytes"))
                                   if self._loop_exec is not None else None)
        st["quant_policy"] = self.quant.describe()
        st["prefill_buckets"] = sorted(self._prefills)
        st["attention_impl"] = self.paged_attn
        st.update(kvc.attention_memory_est(
            self.pool, self.max_slots, self.max_pages_per_slot,
            self.page_size, self.paged_attn))
        st["decode_peak_bytes_est"] = (st["pool_bytes"]
                                       + st["peak_attention_bytes"])
        st["hardware"] = self.obs.profiler.spec.name
        st["roofline"] = self.obs.profiler.summary()
        return st
