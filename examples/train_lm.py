"""End-to-end training driver: a ~100M decoder-only LM on the synthetic
bigram stream, with checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_lm.py --steps 300          # ~100M
    PYTHONPATH=src python examples/train_lm.py --tiny --steps 60    # laptop
"""
import argparse
import time

import jax

from repro.configs.base import ModelConfig
from repro.data import DataConfig, Prefetcher, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.optim import adamw
from repro.runtime import FailoverConfig, Membership
from repro.train.step import TrainConfig
from repro.train.trainer import Trainer, TrainerConfig


def model_100m() -> ModelConfig:
    # ~110M params: 12L x 768, GQA 12/4, ff 3072, 32k vocab
    return ModelConfig(name="lm-100m", family="dense", num_layers=12,
                       d_model=768, num_heads=12, num_kv_heads=4,
                       d_ff=3072, vocab=32768, remat="none", loss_chunk=128)


def model_tiny() -> ModelConfig:
    return ModelConfig(name="lm-tiny", family="dense", num_layers=4,
                       d_model=128, num_heads=4, num_kv_heads=2,
                       d_ff=512, vocab=2048, remat="none", loss_chunk=64)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = model_tiny() if args.tiny else model_100m()
    model = Model(cfg)
    print(f"{cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    membership = Membership()
    for i in range(4):
        membership.request_join(f"10.0.0.{i}", 7000)

    trainer = Trainer(
        model,
        TrainerConfig(steps=args.steps, log_every=10,
                      train=TrainConfig(opt=adamw.OptConfig(
                          peak_lr=3e-4, warmup_steps=20,
                          total_steps=args.steps)),
                      failover=FailoverConfig(args.ckpt_dir,
                                              save_every_steps=50)),
        membership=membership, model_axis=1)

    data = Prefetcher(iter(SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=0))), depth=2)

    state = trainer.init_state(jax.random.PRNGKey(0))
    t0 = time.time()
    trainer.fit(state, data)
    for rec in trainer.history:
        print(rec)
    print(f"done in {time.time()-t0:.1f}s; "
          f"loss {trainer.history[0]['loss']:.3f} -> "
          f"{trainer.history[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
