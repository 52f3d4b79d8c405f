"""Family → module dispatch.  Every family exposes the same surface:

    init_params(cfg, key)
    forward(params, cfg, tokens, **kw)        -> (logits, aux)
    loss_fn(params, cfg, tokens, labels)      -> scalar
    init_decode_cache(cfg, batch, max_len)    -> cache pytree
    decode_step(params, cfg, cache, tokens)   -> (logits, cache)

The decoder families add ``prefill(params, cfg, cache, tokens, length)``:
one pass over a prompt into a paged K/V cache.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax.numpy as jnp

from repro.models import decoder, encdec, hybrid, xlstm_model
from repro.models.config import ModelConfig


def _decoder_api():
    return SimpleNamespace(
        init_params=decoder.init_params,
        forward=decoder.forward,
        loss_fn=decoder.loss_fn,
        init_decode_cache=decoder.init_decode_cache,
        prefill=decoder.prefill,
        decode_step=decoder.decode_step,
    )


_FAMILIES = {
    "dense": _decoder_api(),
    "moe": _decoder_api(),
    "mla_moe": _decoder_api(),
    "hybrid": SimpleNamespace(
        init_params=hybrid.init_params,
        forward=hybrid.forward,
        loss_fn=hybrid.loss_fn,
        init_decode_cache=hybrid.init_decode_cache,
        decode_step=hybrid.decode_step,
    ),
    "xlstm": SimpleNamespace(
        init_params=xlstm_model.init_params,
        forward=xlstm_model.forward,
        loss_fn=xlstm_model.loss_fn,
        init_decode_cache=xlstm_model.init_decode_cache,
        decode_step=xlstm_model.decode_step,
    ),
    "encdec": SimpleNamespace(
        init_params=encdec.init_params,
        forward=encdec.forward,
        loss_fn=encdec.loss_fn,
        init_decode_cache=encdec.init_decode_cache,
        decode_step=encdec.decode_step,
    ),
}


def model_for(cfg: ModelConfig):
    return _FAMILIES[cfg.family]
