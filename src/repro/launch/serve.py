"""Serving launcher: batch-synchronous or continuous-batching engine for any
assigned architecture.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --engine continuous --page-size 16 --max-tokens-in-flight 512
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs.registry import ARCH_IDS, get_config, get_smoke_config
from ..models.registry import build_model
from ..obs import Obs, resolve_hardware
from ..obs.chrometrace import write_trace
from ..quant import QuantPolicy
from ..roofline.analysis import HARDWARE_PRESETS
from ..serve.engine import ContinuousEngine, Engine, Request
from ..serve.kvcache import servable_reasons
from . import mesh as mesh_lib
from .cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="batch engine: batch size; continuous: decode slots")
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed (reproducible per engine)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="early-exit the device decode loop at this token")
    ap.add_argument("--engine", default="batch",
                    choices=["batch", "continuous"],
                    help="batch-synchronous engine or the continuous-"
                         "batching engine over the paged KV pool")
    ap.add_argument("--page-size", type=int, default=16,
                    help="continuous: KV pool page size (tokens per block)")
    ap.add_argument("--max-tokens-in-flight", type=int, default=None,
                    help="continuous: admission token budget")
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="continuous: decode steps per device dispatch")
    ap.add_argument("--admission", default="optimistic",
                    choices=["optimistic", "reserve"],
                    help="continuous: optimistic page admission (preempt on "
                         "exhaustion) or legacy worst-case reservation")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="continuous: bounded submit queue; requests beyond "
                         "it are REJECTED (backpressure)")
    ap.add_argument("--max-preemptions", type=int, default=4,
                    help="continuous: per-request preemption bound before a "
                         "slot stalls instead of thrashing")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="continuous: per-request deadline (seconds from "
                         "arrival); expired requests go terminal TIMEOUT")
    ap.add_argument("--paged-attn", default="stream",
                    choices=["stream", "gather"],
                    help="continuous: fused paged flash-decode (default) or "
                         "the legacy gather-then-attend oracle path")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="continuous: paged KV-pool storage dtype; int8 "
                         "adds per-(page, head) absmax scales and halves-"
                         "to-quarters pool bytes (repro.quant)")
    ap.add_argument("--quant-weights", action="store_true",
                    help="quantize the precomputed spectral weight planes "
                         "to fixed point (per-block-row absmax scales)")
    ap.add_argument("--weight-bits", type=int, default=8, choices=[8, 4],
                    help="with --quant-weights: int8 planes or the packed-"
                         "int4 stretch mode (two nibbles per byte)")
    ap.add_argument("--decode-mode", default="scan",
                    choices=["scan", "per_token"],
                    help="batch engine: device-resident loop (default) or "
                         "the seed per-token host loop")
    ap.add_argument("--no-bucket", action="store_true",
                    help="batch engine: disable prompt-length bucketing")
    ap.add_argument("--no-precompute", action="store_true",
                    help="skip the offline spectral-weight pass")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write repro.obs JSONL telemetry (registry "
                         "snapshots + per-request traces) to FILE; validate "
                         "with python -m repro.obs.emit --validate FILE")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="with --metrics-out: flush every N engine "
                         "dispatches (default 10)")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable traces/histograms (counters stay live; "
                         "the zero-overhead telemetry path)")
    ap.add_argument("--shadow-sample", type=float, default=0.0,
                    metavar="FRAC",
                    help="continuous: replay this fraction of FINISHED "
                         "requests through the f32 dense-cache oracle "
                         "between dispatches, publishing online "
                         "health.greedy_agreement / health.logit_drift "
                         "(obs/health.py)")
    ap.add_argument("--slo", action="store_true",
                    help="run the stock SLO watchdog (obs/slo.py) over "
                         "every emitted snapshot; fired alerts are "
                         "appended to --metrics-out as alert records and "
                         "summarized on exit")
    ap.add_argument("--slo-rules", default=None, metavar="RULES.json",
                    help="with --slo: JSON list of Rule dicts instead of "
                         "the stock ruleset")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write a Perfetto-loadable Chrome trace of the "
                         "serve (engine dispatch lanes, one lane per "
                         "request, counter tracks) to FILE; open at "
                         "https://ui.perfetto.dev")
    ap.add_argument("--replicas", type=int, default=1,
                    help="continuous: serve through a replicated fleet of N "
                         "engines behind the health-checked failover router "
                         "(repro.fleet); telemetry gains a replica= label "
                         "and per-replica trace lanes")
    ap.add_argument("--router-policy", default="jsq",
                    choices=["jsq", "round_robin"],
                    help="with --replicas: join-shortest-queue placement "
                         "(default) or round-robin")
    ap.add_argument("--hedge-after", type=float, default=None,
                    metavar="SECONDS",
                    help="with --replicas: hedge a request to a second "
                         "replica if its first token takes longer than this "
                         "(default: adaptive, 4x the fleet's p99 TTFT)")
    ap.add_argument("--hardware", default="auto",
                    choices=["auto"] + sorted(HARDWARE_PRESETS),
                    help="roofline HardwareSpec the profiler attributes "
                         "dispatches against (auto = detect jax backend)")
    return ap


def quant_policy(args) -> QuantPolicy:
    return QuantPolicy(kv_dtype=args.kv_dtype,
                       quant_weights=args.quant_weights,
                       weight_bits=args.weight_bits)


def continuous_engine(cfg, params, args, obs, *, max_seq: int,
                      device=None) -> ContinuousEngine:
    """The continuous engine this launcher serves with, placed on one
    ``device`` (default: the first)."""
    return ContinuousEngine(
        cfg, params, max_slots=args.max_batch, max_seq=max_seq,
        page_size=args.page_size,
        max_tokens_in_flight=args.max_tokens_in_flight,
        decode_chunk=args.decode_chunk, sample=args.sample,
        seed=args.seed, eos_id=args.eos_id,
        mesh=mesh_lib.make_device_mesh(device),
        precompute=not args.no_precompute,
        paged_attn=args.paged_attn,
        quant=quant_policy(args), obs=obs, admission=args.admission,
        max_queue=args.max_queue,
        max_preemptions=args.max_preemptions,
        shadow_sample=args.shadow_sample)


def replica_router(cfg, params, args, obs, *, max_seq: int):
    """``args.replicas`` continuous engines behind the failover router.
    Replica i owns device i; replicas share devices round-robin only where
    there are fewer devices than replicas (a one-device CPU host)."""
    from ..fleet import EngineReplica, Router
    devices = jax.devices()
    pool = [EngineReplica(f"r{i}", continuous_engine(
                cfg, params, args, obs.scoped(replica=f"r{i}"),
                max_seq=max_seq, device=devices[i % len(devices)]))
            for i in range(args.replicas)]
    return Router(pool, policy=args.router_policy,
                  hedge_after_s=args.hedge_after, obs=obs, seed=args.seed)


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    getter = get_config if args.full else get_smoke_config
    cfg = getter(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq = 64 + args.new_tokens
    watchdog = None
    if args.slo:
        from ..obs.slo import SloWatchdog, rules_from_json
        watchdog = SloWatchdog(rules_from_json(args.slo_rules)
                               if args.slo_rules else None)
    obs = Obs(enabled=not args.no_obs, emit_path=args.metrics_out,
              emit_every=args.metrics_every,
              hardware=resolve_hardware(args.hardware), slo=watchdog)
    router = None
    if args.replicas > 1 and args.engine != "continuous":
        raise SystemExit("[launch.serve] --replicas > 1 requires "
                         "--engine continuous")
    if args.engine == "continuous":
        reasons = servable_reasons(cfg)
        if reasons:
            raise SystemExit(f"[launch.serve] {args.arch} is not continuous-"
                             f"servable ({'; '.join(reasons)}); "
                             f"use --engine batch")
        if args.replicas > 1:
            router = replica_router(cfg, params, args, obs, max_seq=max_seq)
        else:
            engine = continuous_engine(cfg, params, args, obs,
                                       max_seq=max_seq)
    else:
        if args.kv_dtype != "f32":
            print(f"[launch.serve] note: --kv-dtype {args.kv_dtype} applies "
                  f"to the continuous engine's paged pool; the batch "
                  f"engine's dense cache stays f32 (parity oracle)")
        if args.shadow_sample > 0.0:
            print("[launch.serve] note: --shadow-sample applies to the "
                  "continuous engine (the batch engine IS the f32 oracle)")
        engine = Engine(cfg, params, max_batch=args.max_batch,
                        max_seq=max_seq, sample=args.sample,
                        precompute=not args.no_precompute,
                        decode_mode=args.decode_mode, eos_id=args.eos_id,
                        seed=args.seed, bucket_prompts=not args.no_bucket,
                        quant=quant_policy(args), obs=obs)
    rng = np.random.RandomState(0)
    # prompts cover the smoke sliding window (16): the ring-buffer prefill
    # keeps the window tail and needs S >= window for SWA archs
    reqs = [Request(prompt=rng.randint(0, cfg.vocab_size, size=rng.randint(
        16, 32)).astype(np.int32), max_new_tokens=args.new_tokens, id=i,
        deadline_s=args.deadline_s)
        for i in range(args.requests)]
    t0 = time.time()
    server = router if router is not None else engine
    results = server.generate(reqs)
    dt = time.time() - t0
    toks = sum(r["decode_len"] for r in results)
    # unserved terminals (TIMEOUT/REJECTED/...) carry no prefill span
    served = [r for r in results if r.get("prefill_s") is not None]
    pre = sum(r["prefill_s"] for r in served) / max(len(served), 1)
    deco = sum(r["decode_s"] for r in served) / max(len(served), 1)
    label = (f"{args.engine} x{args.replicas}" if router is not None
             else args.engine)
    print(f"[launch.serve] {args.arch} ({label}): {len(results)} "
          f"requests, {toks} tokens, {dt:.2f}s ({toks / dt:.1f} tok/s; "
          f"mean prefill {pre * 1e3:.0f}ms / decode {deco * 1e3:.0f}ms)")
    if router is not None:
        rs = router.stats()
        nonzero = {s: n for s, n in rs["statuses"].items() if n}
        print(f"[launch.serve] fleet: policy={rs['policy']} "
              f"live={rs['live_replicas']}/{len(router.replicas)} "
              f"placed={rs['placed']} retries={rs['place_retries']} "
              f"hedges={rs['hedges']} failovers={rs['failovers']} "
              f"migrated={rs['migrated_requests']} shed={rs['shed']} "
              f"statuses={nonzero}")
        for rep in rs["replicas"]:
            e = rep["engine"]
            print(f"[launch.serve]   {rep['name']}: {rep['state']} "
                  f"served_statuses="
                  f"{ {s: n for s, n in e['statuses'].items() if n} } "
                  f"preempted={e['preempted']} "
                  f"peak_pages={e['peak_pages_in_use']}")
        router.drain()
    st = server.stats() if router is None else None
    if st is not None and args.engine == "continuous":
        print(f"[launch.serve] telemetry: queue_depth={st['queue_depth']} "
              f"peak_tokens_in_flight={st['peak_tokens_in_flight']} "
              f"peak_pages={st['peak_pages_in_use']}/{engine.num_pages - 1} "
              f"pool={st['pool_bytes'] / 1e6:.1f}MB "
              f"prefill/decode split={st['prefill_s']:.2f}s/"
              f"{st['decode_s']:.2f}s "
              f"dispatches={st['decode_dispatches']} "
              f"buckets={st['prefill_buckets']}")
        print(f"[launch.serve] memory: attn={st['attention_impl']} "
              f"attn_bytes/token={st['attention_bytes_per_token'] / 1e6:.2f}MB "
              f"peak_attn={st['peak_attention_bytes'] / 1e6:.2f}MB "
              f"decode_peak_est={st['decode_peak_bytes_est'] / 1e6:.1f}MB")
        qp = st["quant_policy"]
        print(f"[launch.serve] quant: kv_dtype={qp['kv_dtype']} "
              f"weights={'int' + str(qp['weight_bits']) if qp['quant_weights'] else 'f32'} "
              f"kv_pool_bytes={st['kv_pool_bytes'] / 1e6:.1f}MB")
        nonzero = {s: n for s, n in st["statuses"].items() if n}
        print(f"[launch.serve] lifecycle: statuses={nonzero} "
              f"admission={st['admission']} preempted={st['preempted']} "
              f"stalled={st['stalled']} anomalies={st['anomalies']}")
        print(f"[launch.serve] pool pressure: free_pages={st['free_pages']} "
              f"min_free_pages={st['min_free_pages']} (low-water headroom "
              f"of {engine.num_pages - 1} usable)")
        if st.get("health") is not None:
            h = st["health"]
            print(f"[launch.serve] health: nonfinite_dispatches="
                  f"{h['nonfinite_dispatches']} "
                  f"act_absmax_peak={h['act_absmax_peak']} "
                  f"kv_clip_rate={st['kv_clip_rate']}")
        if st.get("shadow_oracle") is not None:
            sh = st["shadow_oracle"]
            agree = sh["greedy_agreement"]
            drift = sh["logit_drift"]
            print(f"[launch.serve] shadow oracle: sampled={sh['sampled']} "
                  f"replays={sh['replays']} dropped={sh['dropped']} "
                  f"greedy_agreement="
                  f"{'n/a' if agree is None else f'{agree:.4f}'} "
                  f"logit_drift="
                  f"{'n/a' if drift is None else f'{drift:.4g}'}")
    elif st is not None:
        print(f"[launch.serve] telemetry: batches={st['batches']} "
              f"prompt_pad_waste={st['prompt_pad_waste']} tokens "
              f"prefill/decode split={st['prefill_s']:.2f}s/"
              f"{st['decode_s']:.2f}s")
    if not args.no_obs and st is not None and st.get("roofline"):
        print(f"[launch.serve] roofline ({st['hardware']}):")
        for kind, r in st["roofline"].items():
            if not r["dispatches"]:
                continue
            print(f"  {kind:<22} n={r['dispatches']:<4} "
                  f"{r['achieved_flops_per_s'] / 1e9:8.2f} GFLOP/s  "
                  f"{r['achieved_bytes_per_s'] / 1e9:8.2f} GB/s  "
                  f"frac={r['roofline_frac']:.3g} ({r['bound']}-bound)")
    if args.metrics_out is not None:
        obs.close()                        # final snapshot + trailing traces
        print(f"[launch.serve] metrics: {obs.emitter.lines_written} "
              f"lines -> {args.metrics_out}")
    if watchdog is not None:
        ws = watchdog.stats()
        print(f"[launch.serve] slo: {ws['alerts']} alerts "
              f"({ws['page_alerts']} page) by_rule={ws['by_rule']}")
        for a in watchdog.alerts:
            print(f"[launch.serve]   {a['severity'].upper()} {a['rule']} "
                  f"{a['series']}: {a['value']:.6g} {a['op']} "
                  f"{a['threshold']:.6g}")
    if args.trace_out is not None:
        trace = write_trace(obs, args.trace_out,
                            extra_meta={"arch": args.arch,
                                        "engine": args.engine,
                                        "replicas": args.replicas})
        print(f"[launch.serve] chrome trace: "
              f"{len(trace['traceEvents'])} events -> {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")
    if not args.no_obs:
        print("[launch.serve] obs summary:")
        print(obs.summary())


if __name__ == "__main__":
    main()
