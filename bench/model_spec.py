"""A configuration file's model, read the same way by the cost functions and
the plain reference.  It reads only the file, never the program."""

from __future__ import annotations

import dataclasses
from typing import Any

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm: str            # "layer_norm" | "rms_norm"
    norm_eps: float
    mlp: str             # "gelu_tanh" (two matrices) | "swiglu" (three)
    bias: bool           # biases on every linear layer
    window: int          # 0: full attention
    tie_embeddings: bool
    dtype: str

    @classmethod
    def from_config(cls, c: dict[str, Any]) -> "ModelSpec":
        act = c["hidden_act"]
        mlp = {"gelu_pytorch_tanh": "gelu_tanh", "silu": "swiglu"}.get(act)
        if mlp is None:
            raise ValueError(f"hidden_act {act!r} has no reference MLP")
        norm = c["norm"]
        if norm not in ("layer_norm", "rms_norm"):
            raise ValueError(f"norm {norm!r} has no reference")
        return cls(
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]), norm=norm,
            norm_eps=float(c["norm_eps"]), mlp=mlp,
            bias=bool(c.get("use_bias", False)),
            window=int(c.get("sliding_window") or 0),
            tie_embeddings=bool(c["tie_word_embeddings"]), dtype=c["dtype"])

    @property
    def dtype_bytes(self) -> int:
        return _DTYPE_BYTES[self.dtype]
