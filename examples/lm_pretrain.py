"""LM pretraining driver over the assigned architecture zoo.

Runs the REAL distributed training loop (grad accumulation, remat,
checkpoint/restart, deterministic sharded data pipeline) for any of the 10
assigned archs.  On this CPU container use --reduced (same family/block
pattern at smoke scale); on a pod the same entry point runs the full config
under the production mesh (launch/train.py).

Run:  PYTHONPATH=src python examples/lm_pretrain.py --arch xlstm-125m \
          --reduced --steps 50 [--ckpt-dir /tmp/ckpt --resume]
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=list_archs())
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} family={cfg.family} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size} "
          f"(reduced={args.reduced})")

    rep = train_loop(cfg, steps=args.steps, batch=args.batch,
                     seq_len=args.seq_len, lr=args.lr,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     resume=args.resume, log_every=10)
    print(f"\nloss: {rep.losses[0]:.4f} -> {rep.losses[-1]:.4f} "
          f"({len(rep.losses)} steps, resumed from {rep.resumed_from})")
    print(f"mean step time: {np.mean(rep.step_times[1:]) * 1e3:.1f} ms; "
          f"checkpoints written: {rep.checkpoints}")
    assert rep.losses[-1] < rep.losses[0], "training should reduce loss"


if __name__ == "__main__":
    main()
