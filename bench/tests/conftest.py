"""Small benchmark roots for the CPU tests: a copy of ``bench/`` with the
configuration files cut to widths a test can hold."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the program's own small widths for each architecture, and the mix the
# tests serve: prompts and answers that fit a 64-token context
SMALL = dict(n_layers=2, d_model=64, n_heads=4, head_dim=16, d_ff=128,
             vocab_size=128, kv_page_tokens=16, dtype="float32")
SMALL_MIX = {
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 4, "max": 40},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 16},
    "sizes": 6, "pool_share": 1.0, "about": "test"}


def file_config(arch: str, **over) -> dict:
    """A configuration file for the program's ``arch`` with ``over`` set on
    its config, its keys written from that config so that the harness's
    cross-check holds."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(arch), **over)
    return {
        "arch": arch, "overrides": over,
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "hidden_act": {"gelu": "gelu_pytorch_tanh", "silu": "silu"}[cfg.act],
        "norm": {"ln": "layer_norm", "rms": "rms_norm"}[cfg.norm],
        "norm_eps": cfg.norm_eps, "use_bias": cfg.attn_bias,
        "sliding_window": cfg.sliding_window or None,
        "tie_word_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype,
        "max_batch": 2, "max_len": 64}


def small_config(arch: str, **widths) -> dict:
    """A configuration file for ``arch`` at small widths."""
    from repro.configs import get_config
    over = {**SMALL, **widths}
    cfg = get_config(arch)
    over["n_kv_heads"] = min(cfg.n_kv_heads, over["n_heads"])
    if cfg.sliding_window:
        over["sliding_window"] = 24
    return file_config(arch, **over)


def tiny_spec(window: int = 0):
    """A model small enough to count its costs by hand: per layer 192
    attention and 256 MLP weights, a tied head of 8 × 32, bf16."""
    from bench.model_spec import ModelSpec
    return ModelSpec(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                     head_dim=4, d_ff=16, vocab=32, rope_theta=1e4,
                     norm="layer_norm", norm_eps=1e-5, mlp="gelu_tanh",
                     bias=False, window=window, tie_embeddings=True,
                     dtype="bfloat16")


def make_root(tmp: Path, configs: dict[str, dict], mixes: dict[str, dict],
              cells: list[tuple[str, str, str]], limit: float) -> Path:
    """A benchmark root under ``tmp``: this repo's ``bench/`` and
    ``BENCHMARK.json``, with the given configurations, mixes and cells
    (name, config, traffic) in place of its own."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = []
    for name, conf in configs.items():
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(conf))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
    for name, mix in mixes.items():
        (tmp / "bench" / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    bench["workloads"] = []
    for name, conf, traffic in cells:
        bench["workloads"].append({"name": name, "config": conf,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        (tmp / "bench" / "limits" / f"{name}.json").write_text(json.dumps(
            {"worst_logit_gap": {"limit": limit}}))
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    """Keep the tests' programs out of the checkout's compile cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxc"))
