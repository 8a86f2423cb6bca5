"""Batched serving demo: continuous batching with prefill/decode split on
a smoke-scale model (every family supported — KV-cache transformer, SSM
state decode, enc-dec with cross-attention cache).

    PYTHONPATH=src python examples/serve_lm.py --arch qwen1p5_0p5b
    PYTHONPATH=src python examples/serve_lm.py --arch falcon_mamba_7b
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import api
from repro.runtime import Request, ServeEngine
from repro.jax_compat import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1p5_0p5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    extras = None
    if cfg.family == "vlm":
        extras = {"patch_embeds": rng.standard_normal(
            (cfg.n_patches, cfg.d_model), dtype=np.float32)}
    if cfg.family == "encdec":
        extras = {"frames": rng.standard_normal(
            (cfg.enc_positions, cfg.d_model), dtype=np.float32)}

    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, 8 + 4 * (i % 3),
                                        dtype=np.int32),
                    max_new_tokens=args.max_new, extras=extras)
            for i in range(args.requests)]
    engine = ServeEngine(cfg, params, max_seq=96,
                         temperature=args.temperature)
    t0 = time.perf_counter()
    results = engine.serve(reqs)
    dt = time.perf_counter() - t0
    tok = sum(len(r.tokens) for r in results)
    print(f"[serve_lm] {cfg.name}: {len(results)} requests, {tok} tokens, "
          f"{dt:.2f}s ({tok / dt:.1f} tok/s)")
    for r in results:
        print(f"  uid={r.uid}: {r.tokens.tolist()}")


if __name__ == "__main__":
    use_compile_cache()
    main()
