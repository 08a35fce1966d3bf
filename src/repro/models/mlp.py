"""Feed-forward blocks: SwiGLU (3-matrix) and classic 2-matrix MLPs."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import Params, activation, dense_init
from repro.telemetry.spans import MLP


def mlp_init(key, d_model: int, d_ff: int, act: str = "silu",
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    if act == "silu":  # SwiGLU
        return {
            "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype),
        }
    return {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
    }


@jax.named_scope(MLP)
def mlp_apply(p: Params, x: jax.Array, act: str = "silu") -> jax.Array:
    fn = activation(act)
    if "w_gate" in p:
        return (fn(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return fn(x @ p["w_up"]) @ p["w_down"]
