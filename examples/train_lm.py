"""End-to-end LM training driver: data pipeline -> sharded/jitted train
step -> fault-tolerant trainer with checkpointing -> loss curve.

Default scale is CPU-friendly (a reduced qwen-family config, a few hundred
steps).  ``--hundred-m`` switches to a ~100M-parameter config (the scale
called for on real hardware; expect minutes/step on CPU).

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --arch minicpm_2b --steps 50
"""

import argparse

from repro.checkpoint import Checkpointer
from repro.configs import get_smoke_config
from repro.data import SyntheticPipeline
from repro.runtime import Trainer, TrainerConfig
from repro.jax_compat import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1p5_0p5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--hundred-m", action="store_true",
                    help="~100M-param config (slow on CPU)")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "bf16", "int8"])
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if args.hundred_m:
        cfg = cfg.replace(n_layers=12, d_model=768, n_heads=12, n_kv=12,
                          d_ff=2048, vocab=32768, attn_q_chunk=512,
                          attn_kv_chunk=512)
    import jax
    n_params = sum(
        p.size for p in jax.tree.leaves(jax.eval_shape(
            lambda: __import__("repro.models.api", fromlist=["api"])
            .init_params(cfg, jax.random.PRNGKey(0)))))
    print(f"[train_lm] arch={cfg.name} params={n_params / 1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    pipe = SyntheticPipeline(cfg, args.batch, args.seq)
    tcfg = TrainerConfig(total_steps=args.steps, checkpoint_every=50,
                         log_every=20, lr=3e-3, warmup=20,
                         grad_compress=args.grad_compress)
    trainer = Trainer(cfg, tcfg, pipe, Checkpointer(args.ckpt, keep_last=2))
    state, status = trainer.run()
    losses = [m["loss"] for m in trainer.metrics_log]
    if len(losses) >= 2:
        print(f"[train_lm] {status}: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
              f"over {int(state['step'])} steps "
              f"({'LEARNING' if losses[-1] < losses[0] else 'check config'})")


if __name__ == "__main__":
    use_compile_cache()
    main()
