"""Training launcher: ``--arch <id>`` selects any assigned architecture.

Host-scale (this container) runs the REDUCED same-family config by default;
``--full`` selects the published config (for multi-host TPU launches — the
same entrypoint, the mesh comes from the environment).

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse

import jax

from ..configs.registry import ARCH_IDS, get_config, get_smoke_config
from ..data.pipeline import SyntheticLM
from ..dist import ctx as dist_ctx
from ..obs import Obs
from ..optim import adamw
from ..train.trainer import Trainer
from . import mesh as mesh_lib
from .cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--full", action="store_true",
                    help="published config (TPU-scale launch)")
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--bayesian", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--workdir", default="/tmp/repro_launch_train")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write repro.obs JSONL telemetry (train.loss / "
                         "train.step_s / train.tokens_per_s snapshots) to "
                         "FILE; the heartbeat file is unaffected")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="with --metrics-out: flush every N steps")
    args = ap.parse_args()
    enable_compile_cache()

    getter = get_config if args.full else get_smoke_config
    cfg = getter(args.arch, compress=not args.no_compress)
    data = SyntheticLM(cfg, batch=args.batch, seq=args.seq, seed=0)
    obs = Obs(emit_path=args.metrics_out, emit_every=args.metrics_every)
    trainer = Trainer(
        cfg,
        adamw.AdamWConfig(lr=args.lr, quantize_moments=args.int8_moments),
        workdir=args.workdir, data_fn=data, total_steps=args.steps,
        ckpt_every=max(args.steps // 2, 1), log_every=10, accum=args.accum,
        compress_grads=args.compress_grads, bayesian_mode=args.bayesian,
        obs=obs)
    # The step jit traces lazily (first call inside run()), so installing the
    # activation policy here pins block-boundary activations for the whole run.
    with dist_ctx.activation_policy(mesh_lib.make_host_mesh()):
        state = trainer.run()
    n = sum(p.size for p in jax.tree.leaves(state["params"]))
    loss = (f"{trainer.history[-1]['loss']:.4f}" if trainer.history
            else "n/a (fewer steps than log_every)")
    print(f"[launch.train] {args.arch}: {int(state['step'])} steps, "
          f"{n:,} params, loss {loss}")
    if args.metrics_out is not None:
        obs.close()                         # final cumulative snapshot
        print(f"[launch.train] metrics: {obs.emitter.lines_written} "
              f"lines -> {args.metrics_out}")


if __name__ == "__main__":
    main()
