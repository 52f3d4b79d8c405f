#!/usr/bin/env python3
"""Serve StarCoder2-3B at its published widths on one TPU, and check it.

    python chip_smoke.py [--seed N]

Builds the unreduced ``starcoder2_3b`` config (30 layers, d_model 3072,
24/2 heads, vocab 49152, bf16) with random weights from ``--seed`` and
serves six greedy requests of distinct prompt lengths through
``ServingEngine``, built as ``repro.launch.serve`` builds it.  It serves
them twice: with an exact-fit KV frame pool, and with a pool too small for
the batch, which must spill pages and fault them back in.  Every served
token is then checked against a teacher-forced ``forward`` over the prompt
and the served tokens.

Everything runs in this one process.  The last line of output is one JSON
object naming the device.  The script exits non-zero, without that line,
when JAX finds no TPU or any phase fails.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.common import random_params, use_compile_cache  # noqa: E402
from repro.launch.serve import make_engine  # noqa: E402
from repro.models.registry import model_for  # noqa: E402

ARCH = "starcoder2_3b"
MAX_BATCH = 4
MAX_LEN = 1024                 # 4 pages of 256 tokens per sequence
MAX_NEW = 16
PROMPT_LENS = (600, 64, 347, 128, 512, 230)
SHORT_POOL = 6                 # frames; the exact fit is MAX_BATCH * 4 = 16
# The reference runs the same bf16 weights through a different program
# (whole-sequence attention instead of one-token paged decode), so its
# logits differ from the served ones by bf16 rounding.  A served token
# passes where its reference logit is within LOGIT_TOL of the reference
# maximum: 8 bf16 ulps at a logit of 4, where the maxima of these
# unit-variance logits sit.  A wrong token is typically several logits off.
LOGIT_TOL = 0.25

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, read per phase."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.total += duration


class Phase:
    """Wall and compile seconds of one phase, printed as it ends."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.clock.total - self.c0
        if exc[0] is None:
            print(f"{self.name}: wall_s={self.wall} compile_s={self.compile}",
                  flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def make_prompts(vocab: int, lens, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def serve(cfg, params, prompts, *, max_batch: int, max_len: int,
          max_new: int, pool_frames):
    """Greedy-serve ``prompts``; return (served tokens, engine stats)."""
    eng = make_engine(cfg, params, max_batch=max_batch, max_len=max_len,
                      pool_frames=pool_frames)
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run_until_done()
    if not all(r.done for r in reqs):
        fail(f"{sum(not r.done for r in reqs)} requests did not finish")
    return [list(r.generated) for r in reqs], eng.stats


@functools.partial(jax.jit, static_argnums=1)
def _logit_gaps(params, cfg, tokens, positions, served):
    """Teacher-forced logits at ``positions``: (max - logit of the served
    token, max), both (R, T)."""
    logits, _ = model_for(cfg).forward(params, cfg, tokens)
    rows = jnp.arange(tokens.shape[0])[:, None]
    at = logits[rows, positions].astype(jnp.float32)          # (R, T, V)
    best = at.max(axis=-1)
    got = jnp.take_along_axis(at, served[..., None], axis=-1)[..., 0]
    return best - got, best


def reference_gaps(cfg, params, prompts, served, *, max_len: int):
    """Run ``forward`` over prompt + served tokens (padded to ``max_len``,
    one compile for all requests); return the (R, T) logit gaps and the
    (R, T) reference maxima."""
    tokens = np.zeros((len(prompts), max_len), np.int32)
    positions = np.zeros((len(prompts), len(served[0])), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, s[:-1]])
        tokens[i, :len(seq)] = seq
        positions[i] = len(p) - 1 + np.arange(len(s))
    gaps, best = _logit_gaps(params, cfg, jnp.asarray(tokens),
                             jnp.asarray(positions),
                             jnp.asarray(np.asarray(served, np.int32)))
    return np.asarray(gaps), np.asarray(best)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found: JAX reports platform "
                         f"'{dev.platform}'; this check runs only on a TPU")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()

    cfg = get_config(ARCH)
    with Phase(clock, "init"):
        params = jax.block_until_ready(random_params(cfg, args.seed))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"params={n_params} param_bytes={n_bytes}", flush=True)

    prompts = make_prompts(cfg.vocab_size, PROMPT_LENS, args.seed)
    runs = {}
    for name, frames in (("exact_pool", None), ("short_pool", SHORT_POOL)):
        with Phase(clock, f"serve {name} (pool_frames={frames or 'exact'})"):
            served, stats = serve(cfg, params, prompts, max_batch=MAX_BATCH,
                                  max_len=MAX_LEN, max_new=MAX_NEW,
                                  pool_frames=frames)
        runs[name] = served
        for p, s in zip(prompts, served):
            print(f"  prompt_tokens={len(p)} new_tokens={len(s)} -> {s}")
        print(f"  decode_steps={stats.decode_steps} "
              f"tokens={stats.tokens_generated} spills={stats.spill_events} "
              f"fault_page_ins={stats.fault_page_ins}", flush=True)
        if any(len(s) != MAX_NEW for s in served):
            fail(f"{name}: a request did not get {MAX_NEW} new tokens")
        if any(not 0 <= t < cfg.vocab_size for s in served for t in s):
            fail(f"{name}: a served token is outside the vocabulary")
        spilled = stats.spill_events > 0 and stats.fault_page_ins > 0
        if frames is None and (stats.spill_events or stats.fault_page_ins):
            fail("exact_pool: the exact-fit pool spilled")
        if frames is not None and not spilled:
            fail("short_pool: the undersized pool neither spilled nor "
                 "faulted pages back in")
    if runs["short_pool"] != runs["exact_pool"]:
        fail("paging changed the served tokens")

    with Phase(clock, "reference"):
        gaps, best = reference_gaps(cfg, params, prompts, runs["exact_pool"],
                                    max_len=MAX_LEN)
    if not np.isfinite(gaps).all() or not np.isfinite(best).all():
        fail("the reference logits are not finite")
    print(f"reference: worst_logit_gap={gaps.max()} tol={LOGIT_TOL} "
          f"served_is_argmax={int((gaps == 0).sum())}/{gaps.size} "
          f"max_logit_range=[{best.min()}, {best.max()}]", flush=True)
    for p, g in zip(prompts, gaps):
        print(f"  prompt_tokens={len(p)} worst_gap={g.max()}")
    if gaps.max() > LOGIT_TOL:
        fail(f"a served token's reference logit is {gaps.max()} below the "
             f"reference maximum (tolerance {LOGIT_TOL})")

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
