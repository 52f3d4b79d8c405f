"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains a reduced config for real (loss curves,
checkpoints); on a TPU slice the same entry point builds the production
mesh, applies the sharding rules, and runs the full config — the dry-run
(launch/dryrun.py) is exactly this path lowered with ShapeDtypeStructs.
"""

from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import ShardInfo, SyntheticLM
from repro.distributed.checkpoint import Checkpointer
from repro.launch.common import random_params, use_compile_cache
from repro.models.config import reduced
from repro.optim.adamw import AdamWConfig
from repro.optim.schedules import cosine_with_warmup
from repro.training.trainer import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_14b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="train the reduced config (CPU default)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, n_layers=4, d_model=128, d_ff=256 if cfg.d_ff else 0,
                      vocab_size=512)
    params = random_params(cfg, args.seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} family={cfg.family} params={n_params:,}")

    tcfg = TrainConfig(
        microbatches=args.microbatches,
        optimizer=AdamWConfig(
            lr=args.lr,
            schedule=cosine_with_warmup(args.lr, 20, args.steps)))
    ds = SyntheticLM(cfg.vocab_size, args.seq, args.batch,
                     ShardInfo(0, 1), seed=args.seed)
    ckpt = Checkpointer() if args.checkpoint_dir else None
    tr = Trainer(cfg, tcfg, params, ds, checkpoint_dir=args.checkpoint_dir,
                 checkpoint_every=args.checkpoint_every, checkpointer=ckpt)
    if args.resume and ckpt is not None:
        restored = ckpt.restore_latest(args.checkpoint_dir, tr.params,
                                       tr.opt_state)
        if restored is not None:
            tr.params, tr.opt_state, tr.step = restored
            print(f"resumed from step {tr.step}")
    hist = tr.run(args.steps, log_every=10)
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
