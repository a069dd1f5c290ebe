"""Batched LM serving demo: the decode engine over any assigned arch.

Shows exact-length request batching, prefill + token-by-token decode with
per-slot EOS, and per-family decode state (KV cache / MLA latent / SSM /
mLSTM matrix memory).  Enc-dec archs get stub audio frames.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch zamba2-2.7b \
          [--requests 6] [--temperature 0.8]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.serve import Request, ServeEngine
from repro.models.lm import get_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-2.7b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()      # CPU container scale
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=4, temperature=args.temperature)

    rng = np.random.default_rng(0)
    lens = rng.choice([8, 8, 12], size=args.requests)   # mixed-length queue
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=args.max_new) for L in lens]

    frames = None
    if cfg.encoder_layers > 0:
        frames = rng.standard_normal(
            (len(reqs), 8, cfg.d_model)).astype(np.float32)
        comps = eng.generate_batch(reqs[:eng.max_batch],
                                   frame_embeds=frames[:eng.max_batch])
    else:
        comps = eng.serve(reqs)

    for i, c in enumerate(comps):
        tps = c.steps / max(c.decode_s, 1e-9)
        print(f"req{i} (len {len(reqs[i].prompt)}): "
              f"tokens={list(c.tokens[:8])}{'...' if len(c.tokens) > 8 else ''} "
              f"prefill={c.prefill_s*1e3:.0f}ms decode={tps:,.0f} tok/s")


if __name__ == "__main__":
    main()
