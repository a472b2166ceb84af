"""End-to-end training driver: train a small LM with the full production
stack — sharded train step, AdamW + cosine schedule, deterministic data
pipeline, async checkpointing, crash recovery.

Default runs a ~7M-parameter qwen-family model for 200 steps on CPU in a
couple of minutes.  ``--arch`` selects any registered architecture;
``--params-100m`` scales to ~100M parameters (the deliverable configuration
— run it on real hardware).

Run:  PYTHONPATH=src python examples/train_tinylm.py [--steps 200]
"""

import argparse

import jax

from repro.configs import base as cb
from repro.data.pipeline import DataConfig
from repro.optim import adamw
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--params-100m", action="store_true",
                    help="~100M-param configuration (use real hardware)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_tinylm")
    args = ap.parse_args()

    cfg = cb.get(args.arch, smoke=True)
    if args.params_100m:
        cfg = cfg.scaled(n_layers=8, d_model=768, n_heads=12, n_kv_heads=12,
                         d_ff=2048, vocab_size=32000)
    else:
        cfg = cfg.scaled(d_model=128, d_ff=384, n_layers=4,
                         vocab_size=2048)
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"devices={len(jax.devices())}")

    tc = TrainerConfig(total_steps=args.steps, ckpt_every=50, log_every=10,
                       ckpt_dir=args.ckpt_dir)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=20,
                            total_steps=args.steps)
    dc = DataConfig(global_batch=args.batch, seq_len=args.seq, seed=0)
    trainer = Trainer(cfg, tc, opt_cfg=opt, data_cfg=dc)

    import logging
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    out = trainer.run()
    print(f"\nfinished at step {out['final_step']}; "
          f"loss {float(out['metrics']['loss']):.4f}; "
          f"restarts {out['restarts']}; stragglers {out['stragglers']}")
    trainer.checkpointer.close()


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
