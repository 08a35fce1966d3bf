"""The decoder of a vision-language model: the plain decoder's layer,
with ``frontend_tokens`` patch embeddings ahead of the text (the vision
encoder stubbed as the program does) and M-RoPE over them."""
from __future__ import annotations

from chipbench.families.dense import block, init, layer_flops, layer_params

__all__ = ["FIELDS", "block", "init", "layer_flops", "layer_params",
           "vision_positions"]

FIELDS = ("mrope_sections", "frontend_tokens")


def vision_positions(cfg, seq: int) -> int:
    """Patch positions ahead of the text; they carry no label."""
    return min(cfg["frontend_tokens"], max(1, seq // 4))
