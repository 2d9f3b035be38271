"""Smoke check: the continuous-batching serving path on a TPU, at qwen3-4b's
published widths (all 36 layers, random weights made from ``--seed``).

  python chip_smoke.py              # one chip: kernel, serve, parity checks
  python chip_smoke.py --chips 4    # only the replicated path: four one-chip
                                    # replicas behind the router vs one engine

One process drives every chip it uses.  It exits non-zero on any failed
check, and before printing anything when JAX finds no TPU.  The lines it
prints are smoke figures (compile seconds, tok/s, drift), not benchmark
figures; the last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-4b"
SLOTS = 4
PAGE = 16
NEW_TOKENS = 32
# eight prompts in three page-count buckets (7, 16 and 25 pages of 16): each
# bucket compiles its own 36-layer prefill program
PROMPT_LENS = (100, 104, 108, 241, 245, 249, 390, 396)
BUCKETS = [7, 16, 25]
MAX_SEQ = 448                  # longest prompt + NEW_TOKENS, page-aligned
# Kernel vs XLA stream: the outputs are convex mixes of O(1) values, and
# either side may round its f32 matmul inputs to bf16 (2^-9 relative).
KERNEL_TOL = 2e-2
# Paged path vs dense f32-cache path, teacher-forced.  Both run their f32
# matmuls (DFT matrices and LM head included) at the TPU's default
# precision, one bf16 pass, and the two attention lowerings round
# differently; with random weights the logits are near-Gaussian, so the
# top-2 margin is a few percent of the logit scale and some near-ties may
# flip.  A wrong page, head or mask moves the logits by their own scale
# and agreement to ~0, far outside these bounds.
DRIFT_FRAC = 0.10              # max |drift| <= this x max |oracle logit|
MIN_AGREEMENT = 0.75


def fail(msg: str):
    raise SystemExit(f"[chip_smoke] FAIL: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def assert_pallas(compiled, what: str) -> None:
    """The compiled program runs a Pallas kernel on the chip."""
    check("tpu_custom_call" in compiled.as_text(),
          f"{what}: no Pallas kernel in the compiled program")


class CompileClock:
    """Seconds JAX spends in the backend compiler, and persistent-cache
    hits, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def requests(cfg, seed: int):
    from repro.serve.engine import Request
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(1, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=NEW_TOKENS, id=i)
            for i, n in enumerate(PROMPT_LENS)]


def launcher_args(*extra):
    from repro.launch import serve
    return serve.build_parser().parse_args(
        ["--arch", ARCH, "--full", "--engine", "continuous",
         "--max-batch", str(SLOTS), "--page-size", str(PAGE), *extra])


def new_obs():
    from repro.obs import Obs, resolve_hardware
    return Obs(hardware=resolve_hardware("auto"))


def check_results(results, cfg, what: str) -> None:
    for r in results:
        check(r["status"].startswith("FINISHED"),
              f"{what}: request {r['id']} ended {r['status']}")
        check(r["decode_len"] == NEW_TOKENS,
              f"{what}: request {r['id']} decoded {r['decode_len']} of "
              f"{NEW_TOKENS} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r["tokens"]),
              f"{what}: request {r['id']} emitted a token outside the "
              f"vocabulary")


def kernel_check(cfg, seed: int) -> None:
    """The compiled paged-attention kernel against the XLA stream lowering
    on one random pool at qwen3-4b's attention widths, read at layer 1 of
    a 2-layer stack."""
    from repro.kernels import ops as kops
    from repro.kernels.paged_attention import paged_attention_stream
    check(kops.kernel_mode() == "tpu", "kernel_mode() is not 'tpu' on a TPU")
    a = cfg.attention
    B, maxp = 8, 32
    P = B * maxp + 1                     # page 0 is the trash page
    rng = np.random.RandomState(seed)
    pos = rng.randint(0, maxp * PAGE, size=B).astype(np.int32)
    pos[0], pos[1], pos[-1] = maxp * PAGE - 1, 0, -1    # full, first, idle
    table = rng.permutation(np.arange(1, P)).reshape(B, maxp).astype(np.int32)
    table[-1] = 0
    q = rng.randn(B, a.num_heads, a.head_dim).astype(np.float32)
    layers, layer = 2, 1
    shape = (layers, P, PAGE, a.num_kv_heads, a.head_dim)
    sshape = (layers, P, a.num_kv_heads)
    lanes = {
        "f32": (rng.randn(*shape).astype(np.float32),
                rng.randn(*shape).astype(np.float32), {}),
        "int8": (rng.randint(-127, 128, shape).astype(np.int8),
                 rng.randint(-127, 128, shape).astype(np.int8),
                 {"k_scale": rng.uniform(0.5, 1.5, sshape)
                  .astype(np.float32) / 127,
                  "v_scale": rng.uniform(0.5, 1.5, sshape)
                  .astype(np.float32) / 127}),
    }
    for lane, (pk, pv, scales) in lanes.items():
        args = [jnp.asarray(x) for x in (q, pk, pv, table, pos)]
        args.append(jnp.int32(layer))
        sc = {k: jnp.asarray(v) for k, v in scales.items()}
        fn = jax.jit(functools.partial(kops.paged_attention, **sc))
        compiled = fn.lower(*args).compile()
        assert_pallas(compiled, f"kernel {lane}")
        got = np.asarray(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(functools.partial(
                paged_attention_stream, **sc))(*args))
        err = float(np.abs(got - want).max())
        print(f"[chip_smoke] kernel {lane}: B={B} Hq={a.num_heads} "
              f"Hkv={a.num_kv_heads} D={a.head_dim} page={PAGE} "
              f"maxp={maxp}: max |kernel - stream| = {err:.3g} "
              f"(tol {KERNEL_TOL})", flush=True)
        check(np.isfinite(got).all(), f"kernel {lane}: non-finite output")
        check(err <= KERNEL_TOL, f"kernel {lane}: error {err} > {KERNEL_TOL}")
        check(not got[-1].any(), f"kernel {lane}: idle slot not zero")


def serve_check(cfg, params, reqs, kv_dtype: str, device, clock):
    """The launcher's continuous engine on one device, serving ``reqs``
    twice: once cold (compiles) and once warm (timed)."""
    from repro.launch import serve
    c0 = clock.seconds
    eng = serve.continuous_engine(cfg, params, launcher_args(
        "--kv-dtype", kv_dtype), new_obs(), max_seq=MAX_SEQ, device=device)
    what = f"serve kv={kv_dtype}"
    t0 = time.perf_counter()
    cold = eng.generate(reqs)
    t_cold = time.perf_counter() - t0
    check_results(cold, cfg, what)
    t0 = time.perf_counter()
    warm = eng.generate(reqs)
    t_warm = time.perf_counter() - t0
    check_results(warm, cfg, what + " (warm)")
    check([r["tokens"] for r in warm] == [r["tokens"] for r in cold],
          f"{what}: a second serve of the same requests emitted other tokens")
    st = eng.stats()
    check(st["anomalies"] == 0, f"{what}: {st['anomalies']} anomalies")
    nonf = st["health"]["nonfinite_dispatches"]
    check(nonf == 0, f"{what}: {nonf} non-finite dispatches")
    check(st["prefill_buckets"] == BUCKETS,
          f"{what}: prefill buckets {st['prefill_buckets']} != {BUCKETS}")
    assert_pallas(eng._loop_exec[0], f"{what} decode program")
    check(placed_on(eng) == {device},
          f"{what}: engine state on {placed_on(eng)}, not {device}")
    toks = sum(r["decode_len"] for r in warm)
    print(f"[chip_smoke] {what}: {len(reqs)} requests x {NEW_TOKENS} tokens, "
          f"{SLOTS} slots, buckets {st['prefill_buckets']} pages: cold "
          f"{t_cold:.2f}s (compile {clock.seconds - c0:.2f}s), warm "
          f"{t_warm:.2f}s = {toks / t_warm:.1f} tok/s; pool "
          f"{st['pool_bytes'] / 1e6:.1f} MB; anomalies 0, non-finite 0",
          flush=True)
    return eng


def placed_on(eng) -> set:
    """Devices holding any of an engine's params or KV pool."""
    return {d for leaf in jax.tree.leaves((eng.params, eng.pool))
            for d in leaf.devices()}


def parity_check(cfg, eng, prompt) -> None:
    """Teacher-forced paged path (the engine's params and pool dtype)
    against the dense f32-cache path, on one prompt."""
    from repro.quant.calibrate import ParityRunner
    runner = ParityRunner(cfg, eng.params, eng.params, policy=eng.quant,
                          page_size=PAGE)
    rep = runner.run(prompt, NEW_TOKENS)
    bound = DRIFT_FRAC * rep["oracle_logit_absmax"]
    print(f"[chip_smoke] parity kv={eng.quant.kv_dtype}: {rep['steps']} "
          f"teacher-forced steps, prompt {len(prompt)}: max_logit_drift "
          f"{rep['max_logit_drift']:.4g} (bound {bound:.4g} = {DRIFT_FRAC} x "
          f"oracle logit absmax {rep['oracle_logit_absmax']:.4g}), "
          f"greedy_agreement {rep['greedy_agreement']:.4f} "
          f"(bound >= {MIN_AGREEMENT})", flush=True)
    check(rep["max_logit_drift"] <= bound, "parity: logit drift over bound")
    check(rep["greedy_agreement"] >= MIN_AGREEMENT,
          "parity: greedy agreement under bound")


def one_chip(cfg, params, reqs, device, clock, seed: int) -> None:
    kernel_check(cfg, seed)
    eng = serve_check(cfg, params, reqs, "f32", device, clock)
    parity_check(cfg, eng, reqs[len(reqs) // 2].prompt)
    del eng
    serve_check(cfg, params, reqs, "int8", device, clock)


def four_replicas(cfg, params, reqs, devices) -> None:
    """``launch.serve --replicas 4``: four one-chip engines behind the
    router, token-identical to one engine serving the same requests."""
    from repro.fleet.replica import DOWN
    from repro.launch import serve
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX has "
                             f"{len(devices)}")
    args = launcher_args("--replicas", "4")
    ref = serve.continuous_engine(cfg, params, args, new_obs(),
                                  max_seq=MAX_SEQ, device=devices[0])
    want = ref.generate(reqs)
    check_results(want, cfg, "one-chip engine")
    del ref
    router = serve.replica_router(cfg, params, args, new_obs(),
                                  max_seq=MAX_SEQ)
    t0 = time.perf_counter()
    got = router.generate(reqs)
    dt = time.perf_counter() - t0
    check_results(got, cfg, "replicas")
    for i, rep in enumerate(router.replicas):
        check(rep.state != DOWN, f"replica {rep.name} went DOWN: "
                                 f"{rep.down_reason}")
        on = placed_on(rep.engine)
        check(on == {devices[i]},
              f"replica {rep.name}: state on {on}, not {devices[i]}")
        served = rep.engine.stats()["requests"]
        print(f"[chip_smoke] replica {rep.name}: {rep.state} on "
              f"{devices[i]}, served {served} requests", flush=True)
    same = sum(g["tokens"] == w["tokens"] for g, w in zip(got, want))
    st = router.stats()
    print(f"[chip_smoke] replicas: {len(reqs)} requests in {dt:.2f}s "
          f"(compiles included), failovers {st['failovers']}, hedges "
          f"{st['hedges']}; {same}/{len(reqs)} token-identical to the "
          f"one-chip engine", flush=True)
    check(same == len(reqs), "replicas: outputs differ from the one-chip "
                             "engine")
    router.drain()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-replica path and the "
                         "one-chip engine it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and pools")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    from repro.configs.registry import get_config
    from repro.launch.cache import enable_compile_cache
    from repro.models.registry import build_model
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"[chip_smoke] device {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    params = build_model(cfg).init(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[chip_smoke] {ARCH}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, {n / 1e6:.1f}M params "
          f"(random, seed {args.seed}), init {time.perf_counter() - t0:.1f}s",
          flush=True)
    reqs = requests(cfg, args.seed)
    if args.chips == 4:
        four_replicas(cfg, params, reqs, devices)
    else:
        one_chip(cfg, params, reqs, dev, clock, args.seed)
    print(f"[chip_smoke] compile seconds {clock.seconds:.2f}, persistent "
          f"cache hits {clock.cache_hits}, wall "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
