"""The one traffic generator: requests drawn from a mix file and a seed.

A mix gives each length as a distribution.  The generator draws a fixed set
of ``sizes`` lengths from it, at the quantiles (i + 1/2) / sizes: each run
of ``sizes`` requests holds every size once.  Their order is fixed too, the
same for every seed, since a window of a closed loop holds only a handful
of requests and which of them fall into it would change the work.  So
every seed does the same work, and what moves a metric between two seeds
is the system, not the draw.  The seed draws the prompts' token ids.
"""

from __future__ import annotations

import statistics
from typing import Any

import numpy as np

ORDER_SEED = 0      # the one order of the sizes, whatever the run's seed


def levels(dist: dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of ``dist``, clipped."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        raw = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        raw = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(int)


def check_mix(mix: dict[str, Any]) -> None:
    if not 0 < mix["pool_share"] <= 1:
        raise ValueError("pool_share lies in (0, 1]")


class RequestStream:
    """Prompts and output lengths in the order a closed loop sends them."""

    def __init__(self, mix: dict[str, Any], vocab: int, seed: int):
        check_mix(mix)
        self.n = int(mix["sizes"])
        self.prompt_levels = levels(mix["prompt_tokens"], self.n)
        self.output_levels = levels(mix["output_tokens"], self.n)
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(ORDER_SEED)
        self._queue: list[tuple[int, int]] = []

    def next(self) -> tuple[np.ndarray, int]:
        """The next request: (prompt token ids, tokens to generate)."""
        if not self._queue:
            p = self.order.permutation(self.prompt_levels)
            o = self.order.permutation(self.output_levels)
            self._queue = list(zip(p.tolist(), o.tolist()))
        n_prompt, n_out = self._queue.pop(0)
        prompt = self.rng.integers(0, self.vocab, size=n_prompt,
                                   dtype=np.int32)
        return prompt, n_out
