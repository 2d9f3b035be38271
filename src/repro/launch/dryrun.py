import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell against the production mesh, with NO device allocation (inputs are
ShapeDtypeStructs).  This proves the distribution config is coherent — a
sharding mismatch, compile-time OOM, or unsupported collective here is a bug
in the system, not an environment problem.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init); smoke tests and benchmarks import the library
normally and see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
"""
import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs.base import ALL_SHAPES, SHAPES_BY_NAME, cell_is_applicable  # noqa: E402
from ..configs.registry import ARCH_IDS, get_config  # noqa: E402
from ..dist import ctx as dist_ctx  # noqa: E402
from ..dist import sharding as sh  # noqa: E402
from ..models import registry as mreg  # noqa: E402
from ..optim import adamw  # noqa: E402
from ..roofline import analysis as roofline  # noqa: E402
from ..serve import decode as serve_decode  # noqa: E402
from ..serve import params as serve_params  # noqa: E402
from ..train import train_step as ts  # noqa: E402
from . import mesh as mesh_lib  # noqa: E402


def state_specs_for(cfg, mesh, strategy):
    """ShapeDtypeStructs + PartitionSpecs of the train state (no alloc)."""
    model = mreg.build_model(cfg)
    opt_cfg = adamw.AdamWConfig(quantize_moments=True)
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    state_shapes = jax.eval_shape(
        lambda p: {"params": p, "opt": adamw.init(p, opt_cfg),
                   "step": jnp.zeros((), jnp.int32),
                   "skipped": jnp.zeros((), jnp.int32)}, params_shapes)
    pspecs = sh.param_specs(params_shapes, mesh, strategy)

    def mv_spec(path, leaf):
        # opt moments mirror the param; scalar scales/counters replicate
        names = tuple(getattr(p, "key", getattr(p, "idx", p)) for p in path)
        if leaf.ndim == 0:
            return jax.sharding.PartitionSpec()
        base = [str(n) for n in names if str(n) not in
                ("mv", "m", "v", "m_s", "v_s")]
        return sh.param_spec(tuple(base), leaf.shape, mesh, strategy)

    opt_specs = jax.tree_util.tree_map_with_path(
        mv_spec, state_shapes["opt"])
    state_spec = {"params": pspecs, "opt": opt_specs,
                  "step": jax.sharding.PartitionSpec(),
                  "skipped": jax.sharding.PartitionSpec()}
    return state_shapes, state_spec, opt_cfg


def lower_cell(arch_id: str, shape_name: str, mesh, strategy: str = "megatron",
               compress: bool = True, donate: bool = True, seq_shard=None,
               accum: int = 4, cfg_override=None):
    """Lower + compile one cell.  Returns (lowered, compiled, meta).

    ``accum``: microbatch gradient-accumulation factor for train cells —
    global batch 256 is stepped as 4 microbatches of 64, bounding live
    activations to fit the 16 GiB HBM (EXPERIMENTS.md §Dry-run).
    """
    cfg = cfg_override or get_config(arch_id, compress=compress)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = cell_is_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": why}
    if accum == 0:
        # ROOFLINE lowering: XLA's cost model counts a while body once, so
        # exact FLOP/collective counts need unrolled layer loops, accum=1,
        # and single-chunk attention/mlstm (fit numbers come from the
        # default scanned+accumulated lowering instead).
        accum = 1
        S = shape.seq_len
        # q chunks stay a PYTHON loop (counted exactly, causal extent
        # savings realized); kv runs as a single scan trip (counted once =
        # counted exactly).
        cfg = cfg.replace(unroll_scan=True, attn_q_chunk=max(S // 4, 1),
                          attn_kv_chunk=max(S, 1), mlstm_chunk=max(S, 1))
    specs = mreg.input_specs(cfg, shape)
    B = shape.global_batch
    if seq_shard is None:
        seq_shard = strategy == "tokenpar" and shape.kind != "decode"

    lsh = jax.sharding.NamedSharding(
        mesh, sh.logits_spec(mesh, B, cfg.padded_vocab()))
    with mesh, dist_ctx.activation_policy(mesh, seq_shard=seq_shard):
        if shape.kind == "train":
            state_shapes, state_spec, opt_cfg = state_specs_for(
                cfg, mesh, strategy)
            step_fn = ts.make_train_step(cfg, opt_cfg, logits_sharding=lsh,
                                         accum=accum)
            in_shardings = (sh.to_shardings(state_spec, mesh),
                            sh.to_shardings(
                                sh.batch_specs(specs["batch"], mesh, B,
                                               seq_shard), mesh))
            out_shardings = (in_shardings[0], None)
            jitted = jax.jit(step_fn, in_shardings=in_shardings,
                             out_shardings=out_shardings,
                             donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_shapes, specs["batch"])
        elif shape.kind == "prefill":
            step_fn = serve_decode.make_prefill_step(cfg, logits_sharding=lsh)
            model = mreg.build_model(cfg)
            # Serve cells lower against the production serving params: the
            # offline spectral planes baked in (paper's offline weight FFT).
            params_shapes = jax.eval_shape(
                lambda: serve_params.precompute_serving_params(
                    model.init(jax.random.PRNGKey(0)), cfg))
            pshard = sh.to_shardings(
                sh.param_specs(params_shapes, mesh, strategy), mesh)
            cshard = sh.to_shardings(
                sh.cache_specs(specs["cache"], mesh, B), mesh)
            bshard = sh.to_shardings(
                sh.batch_specs(specs["batch"], mesh, B, seq_shard), mesh)
            jitted = jax.jit(step_fn,
                             in_shardings=(pshard, bshard, cshard),
                             out_shardings=(None, cshard),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(params_shapes, specs["batch"],
                                   specs["cache"])
        else:  # decode
            step_fn = serve_decode.make_decode_step(cfg, logits_sharding=lsh)
            model = mreg.build_model(cfg)
            params_shapes = jax.eval_shape(
                lambda: serve_params.precompute_serving_params(
                    model.init(jax.random.PRNGKey(0)), cfg))
            pshard = sh.to_shardings(
                sh.param_specs(params_shapes, mesh, strategy), mesh)
            cshard = sh.to_shardings(
                sh.cache_specs(specs["cache"], mesh, B), mesh)
            tshard = sh.to_shardings(
                sh.batch_specs(specs["tokens"], mesh, B), mesh)
            jitted = jax.jit(
                step_fn,
                in_shardings=(pshard, tshard, cshard, None),
                out_shardings=(None, None, cshard),
                donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(params_shapes, specs["tokens"],
                                   specs["cache"], specs["cache_pos"])
        compiled = lowered.compile()
    return lowered, compiled, {"cfg": cfg, "shape": shape}


def run_cell(arch_id, shape_name, mesh, mesh_name, strategy, compress=True,
             accum=4):
    t0 = time.time()
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "strategy": strategy, "compress": compress,
           "lowering": "roofline" if accum == 0 else "production"}
    try:
        lowered, compiled, meta = lower_cell(arch_id, shape_name, mesh,
                                             strategy, compress, accum=accum)
        if lowered is None:
            rec["status"] = "skipped"
            rec["why"] = meta["skipped"]
            return rec
        rec.update(roofline.cell_report(lowered, compiled, meta["cfg"],
                                        meta["shape"], mesh))
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — report, continue the sweep
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--strategy", default="megatron")
    ap.add_argument("--no-compress", action="store_true",
                    help="dense baseline (paper's uncompressed reference)")
    ap.add_argument("--roofline", action="store_true",
                    help="unrolled exact-cost lowering (accum=1; see "
                         "roofline/analysis.py)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = ([s.name for s in ALL_SHAPES] if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for multi in meshes:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi)
        mname = "2x16x16" if multi else "16x16"
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mesh, mname, args.strategy,
                               compress=not args.no_compress,
                               accum=0 if args.roofline else 4)
                status = rec["status"]
                extra = (rec.get("why") or rec.get("error", "")
                         if status != "ok" else
                         f"bytes/dev={rec['bytes_per_device']:.2e} "
                         f"flops/dev={rec['flops_per_device']:.3e}")
                print(f"[{mname}] {a} x {s}: {status} {extra}", flush=True)
                results.append(rec)
                if args.out:                    # incremental: survive kills
                    os.makedirs(os.path.dirname(args.out) or ".",
                                exist_ok=True)
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"\n== dry-run: {n_ok} ok / {n_skip} skipped / {n_fail} FAILED ==")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", args.out)
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
