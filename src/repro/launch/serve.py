"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching engine with a paged, host-spillable KV pool
at the config's published widths (``--reduced`` shrinks them for the CPU),
exercising the thesis mechanism end to end: admission, prefill, pool
exhaustion → spill, re-activation → Touch-Ahead page-in, decode through
the page table.
"""

from __future__ import annotations

import argparse
from typing import Optional

import numpy as np

from repro.api import FaultPolicy, Strategy
from repro.configs import ARCH_IDS, get_config
from repro.launch.common import random_params, use_compile_cache
from repro.models.config import ModelConfig, reduced
from repro.serving.engine import ServingEngine
from repro.serving.sampler import SamplerConfig


def make_engine(cfg: ModelConfig, params, *, max_batch: int, max_len: int,
                pool_frames: Optional[int] = None,
                strategy: Strategy = Strategy.TOUCH_AHEAD,
                lookahead: int = 4, pin_all: bool = False,
                temperature: float = 0.0) -> ServingEngine:
    """``pool_frames=None`` sizes the frame pool exactly for
    ``max_batch`` sequences of ``max_len`` tokens; fewer frames force
    spills and fault-ins."""
    policy = FaultPolicy(strategy=strategy, lookahead=lookahead)
    return ServingEngine(
        cfg, params, max_batch=max_batch, max_len=max_len,
        pool_frames=pool_frames, policy=policy, pin_all=pin_all,
        sampler=SamplerConfig(temperature=temperature))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2_3b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family widths (for the CPU)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--pool-frames", type=int, default=0,
                    help="undersize to force spills (0 = exact fit)")
    ap.add_argument("--strategy", default="touch_ahead",
                    choices=[s.value for s in Strategy])
    ap.add_argument("--lookahead", type=int, default=4,
                    help="pages per fault event (TOUCH_AHEAD_N / STREAM)")
    ap.add_argument("--pin-all", action="store_true",
                    help="pinning baseline: admission-controlled residency")
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    eng = make_engine(
        cfg, random_params(cfg, 0), max_batch=args.max_batch,
        max_len=args.max_len, pool_frames=args.pool_frames or None,
        strategy=Strategy(args.strategy), lookahead=args.lookahead,
        pin_all=args.pin_all, temperature=args.temperature)

    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, size=rng.integers(3, 9)),
                       max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    eng.run_until_done()
    for r in reqs:
        print(f"req {r.req_id}: prompt[{len(r.prompt)}] -> {r.generated}")
    s = eng.stats
    print(f"\nstats: prefills={s.prefills} decode_steps={s.decode_steps} "
          f"tokens={s.tokens_generated} spills={s.spill_events} "
          f"fault_page_ins={s.fault_page_ins}")


if __name__ == "__main__":
    main()
