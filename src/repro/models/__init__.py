"""Model zoo: ``build(cfg, **options)`` returns a ModelBundle for any arch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.transformer import (ModelBundle, build_decoder_lm,
                                      build_hymba_lm, build_rwkv_lm)


def build(cfg: ArchConfig, *, param_dtype=jnp.float32, compute_dtype=None,
          remat: bool = False, impl: str = "auto",
          rolling_decode: bool = False,
          cache_dtype=jnp.bfloat16,
          decode_impl: str = "auto") -> ModelBundle:
    kw = dict(param_dtype=param_dtype, compute_dtype=compute_dtype,
              remat=remat, impl=impl, cache_dtype=cache_dtype)
    if cfg.family == "ssm":
        return build_rwkv_lm(cfg, **kw)
    if cfg.family == "hybrid":
        return build_hymba_lm(cfg, **kw)
    if cfg.family == "audio" or cfg.is_encoder_decoder:
        from repro.models.encdec import build_encdec
        return build_encdec(cfg, **kw)
    # dense / moe / vlm share the decoder-LM assembly
    return build_decoder_lm(cfg, rolling_decode=rolling_decode,
                            decode_impl=decode_impl, **kw)
