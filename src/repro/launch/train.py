"""Training launcher.

CPU-scale end-to-end run (the container):
  PYTHONPATH=src python -m repro.launch.train --arch paper-moe-100m-smoke \\
      --steps 100 --reshape --ckpt-dir /tmp/ck

Cluster-scale (TPU pod; same code path, production mesh + jit step):
  python -m repro.launch.train --arch olmoe-1b-7b --shape train_4k \\
      --mesh single --steps 10000
"""
from __future__ import annotations

import argparse
import json
import time


def build_loop(cfg, *, steps: int, seq_len: int, global_batch: int,
               microbatches: int = 2, lr: float = 3e-4,
               reshape: bool = False, class_alpha: float = 1.5,
               ckpt_dir: str = "", ckpt_every: int = 0,
               resume: bool = False, ep_ranks: int = 2,
               dispatch_select: str = "off"):
    """The TrainLoop this launcher runs: synthetic Zipf-skewed token
    stream, AdamW with warmup, optional Reshape expert-skew mitigation and
    periodic checkpoints (``resume`` recovers from ``ckpt_dir``)."""
    from repro.core.reshape_moe import MoEReshaper
    from repro.core.skew import SkewParams
    from repro.data.synthetic import TokenStream
    from repro.models import lm
    from repro.optim.adamw import AdamWCfg
    from repro.runtime.loop import LoopConfig, TrainLoop
    from repro.runtime.train import TrainHyper

    stream = TokenStream(vocab=cfg.vocab, seq_len=seq_len,
                         global_batch=global_batch, seed=0,
                         class_alpha=class_alpha)
    hyper = TrainHyper(opt=AdamWCfg(lr=lr, warmup_steps=20,
                                    total_steps=max(steps, 100)))
    lc = LoopConfig(microbatches=microbatches, ckpt_every=ckpt_every,
                    ckpt_dir=ckpt_dir or "/tmp/repro_train_ckpt",
                    dispatch_select=dispatch_select)
    reshaper = None
    if reshape and lm.n_moe_layers(cfg):
        reshaper = MoEReshaper(cfg, lm.n_moe_layers(cfg), ep_ranks=ep_ranks,
                               params=SkewParams(eta=0.0, tau=0.2))
    if resume:
        loop = TrainLoop.recover(cfg, stream, hyper, lc, reshaper=reshaper)
        print(f"recovered at step {int(loop.state['step'])}")
        return loop
    return TrainLoop(cfg, stream, hyper, lc, reshaper=reshaper)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-moe-100m-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reshape", action="store_true",
                    help="enable Reshape expert-skew mitigation")
    ap.add_argument("--class-alpha", type=float, default=1.5,
                    help="token-class Zipf skew (drives routing skew)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ep-ranks", type=int, default=2)
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_arch

    loop = build_loop(get_arch(args.arch), steps=args.steps,
                      seq_len=args.seq_len, global_batch=args.global_batch,
                      microbatches=args.microbatches, lr=args.lr,
                      reshape=args.reshape, class_alpha=args.class_alpha,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      resume=args.resume, ep_ranks=args.ep_ranks)
    reshaper = loop.reshaper
    t0 = time.perf_counter()
    hist = loop.run(args.steps)
    dt = time.perf_counter() - t0
    for h in hist[:: max(1, len(hist) // 20)]:
        extra = ""
        if "dropped" in h:
            extra = f" dropped={int(h['dropped'].sum())}"
        print(f"step {h['step']:5d} loss {h['loss']:.4f} "
              f"gnorm {h['grad_norm']:.2f}{extra}")
    print(f"\n{len(hist)} steps in {dt:.1f}s "
          f"({len(hist) / max(dt, 1e-9):.2f} steps/s)")
    if reshaper is not None:
        print(f"reshape iterations: {reshaper.iterations}; "
              f"events: {len(reshaper.events)}")


if __name__ == "__main__":
    main()
