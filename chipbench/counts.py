"""Operations and bytes a training round needs, from the configuration's
shapes alone.  The per-layer metrics divide these by device time.

Operations count each multiply-add as two, over the matrix products and
contractions the layer equations require: projections, attention scores
and values over the keys each query may see (causal, and within the
window where there is one), the MLP, the SSM's projections and
recurrence, and the head over the positions that carry a label.
Norms, softmax and activations are left out, and so is work that the
program repeats to save memory: the backward pass counts as twice the
forward.
"""
from __future__ import annotations

from typing import Dict

from chipbench import families

QINT8_SCALE_BYTES = 4     # one float32 scale per block


def visible_keys(seq: int, window: int) -> int:
    """Sum over query positions of the keys a causal query may see."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def padded_vocab(cfg: Dict) -> int:
    return -(-cfg["vocab_size"] // 128) * 128


def attention_flops(cfg: Dict, seq: int) -> int:
    """GQA projections of ``seq`` positions, and scores and values over
    the keys each query may see."""
    d, h, kv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    proj = 2 * d * h * hd * 2 + 2 * d * kv * hd * 2      # q, o; k, v
    return (proj * seq
            + 4 * h * hd * visible_keys(seq, cfg.get("sliding_window", 0)))


def mlp_flops(cfg: Dict, seq: int) -> int:
    """SwiGLU: gate, up and down projections."""
    return 3 * 2 * cfg["d_model"] * cfg["d_ff"] * seq


def attention_params(cfg: Dict) -> int:
    d, h, kv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    return d * h * hd * 2 + d * kv * hd * 2


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["d_model"] * cfg["d_ff"]


def stack_flops(cfg: Dict, seq: int) -> int:
    """Forward operations of the family's layers over one sequence: its
    own count of its stack, or ``n_layers`` of its one kind of layer."""
    fam = families.of(cfg)
    if hasattr(fam, "stack_flops"):
        return fam.stack_flops(cfg, seq)
    return cfg["n_layers"] * fam.layer_flops(cfg, seq)


def stack_params(cfg: Dict) -> int:
    """Parameters of the family's layers, counted as :func:`stack_flops`
    counts their operations."""
    fam = families.of(cfg)
    if hasattr(fam, "stack_params"):
        return fam.stack_params(cfg)
    return cfg["n_layers"] * fam.layer_params(cfg)


def forward_flops(cfg: Dict, seq: int) -> int:
    """Forward operations of one sequence of ``seq`` positions: the
    family's layers and the head over the positions that carry a
    label."""
    labelled = seq - vision_positions(cfg, seq)
    head = 2 * cfg["d_model"] * padded_vocab(cfg) * labelled
    return stack_flops(cfg, seq) + head


def vision_positions(cfg: Dict, seq: int) -> int:
    """Positions ahead of the text that carry no label (0 where the
    family has none)."""
    fam = families.of(cfg)
    return fam.vision_positions(cfg, seq) if hasattr(
        fam, "vision_positions") else 0


def train_flops_per_round(cfg: Dict, traffic: Dict) -> int:
    """Forward and backward operations of one learner's round."""
    steps = steps_per_round(traffic["plan"])
    return 3 * steps * traffic["batch"] * forward_flops(cfg, traffic["seq"])


def steps_per_round(plan: str) -> int:
    """SGD steps in a round: the outermost level's period."""
    return int(plan.split("/")[-1].split("@")[1].split(":")[0])


def level_fires_per_round(plan: str) -> Dict[str, int]:
    total = steps_per_round(plan)
    return {part.split("@")[0]: total // int(part.split("@")[1].split(":")[0])
            for part in plan.split("/")}


def qint8_pack_bytes(n: int, block: int) -> int:
    """Least HBM bytes to pack ``n`` float32 values: read them, write the
    int8 payload and one float32 scale per block."""
    nb = -(-n // block)
    return 4 * n + n + QINT8_SCALE_BYTES * nb


def qint8_unpack_bytes(n: int, block: int) -> int:
    """Least HBM bytes to unpack: read payload and scales, write float32."""
    return qint8_pack_bytes(n, block)


def param_count(cfg: Dict) -> int:
    """One learner's parameters, as the configuration's training job holds
    them (padded vocabulary rows included)."""
    d, vp = cfg["d_model"], padded_vocab(cfg)
    head = 0 if cfg.get("tie_embeddings") else d * vp
    return stack_params(cfg) + vp * d + head + d
