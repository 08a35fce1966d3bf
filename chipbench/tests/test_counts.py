"""The yardstick's operation and byte counts against hand-computed values
at a tiny configuration, and the peak table's refusal of an unknown
chip."""
from __future__ import annotations

import json

import pytest

from chipbench import counts, device
from conftest import BENCH

TINY = {"family": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2,
        "n_kv_heads": 1, "head_dim": 4, "d_ff": 16, "vocab_size": 100,
        "sliding_window": 0, "tie_embeddings": True}


def test_visible_keys():
    assert counts.visible_keys(4, 0) == 1 + 2 + 3 + 4
    assert counts.visible_keys(4, 2) == 1 + 2 + 2 + 2
    assert counts.visible_keys(4, 8) == 10


def test_decoder_forward_flops_by_hand():
    # per position: q and o 2*8*8 each, k and v 2*8*4 each, SwiGLU 3*2*8*16
    per_pos = 2 * (2 * 8 * 8) + 2 * (2 * 8 * 4) + 3 * 2 * 8 * 16
    attn = 4 * 2 * 4 * 10                 # QK and PV over 10 visible keys
    head = 2 * 8 * 128 * 4                # vocab padded to 128
    assert counts.forward_flops(TINY, 4) == 2 * (per_pos * 4 + attn) + head


def test_hybrid_adds_the_ssm_head():
    hy = dict(TINY, family="hybrid", ssm_state=4, ssm_expand=2,
              sliding_window=2, tie_embeddings=False)
    ssm = (2 * 8 * 32 + 2 * 4 * 16 + 2 * 16 * 9 + 2 * 1 * 16
           + 6 * 16 * 4 + 2 * 16 * 8)
    dense = dict(TINY, sliding_window=2)
    assert counts.forward_flops(hy, 4) == \
        counts.forward_flops(dense, 4) + 2 * ssm * 4


def test_vision_positions_carry_no_head():
    vlm = dict(TINY, family="vlm", frontend_tokens=256)
    # seq 8: 2 patch positions, 6 labelled
    assert counts.forward_flops(vlm, 8) == \
        counts.forward_flops(TINY, 8) - 2 * 8 * 128 * 2


def test_round_flops_and_plan():
    job = {"plan": "local@1/global@8", "batch": 2, "seq": 4}
    assert counts.steps_per_round(job["plan"]) == 8
    assert counts.train_flops_per_round(TINY, job) == \
        3 * 8 * 2 * counts.forward_flops(TINY, 4)
    assert counts.level_fires_per_round("local@1:qint8:128/global@8") == \
        {"local": 8, "global": 1}


def test_qint8_bytes_by_hand():
    # 300 floats read, 300 int8 and three 4-byte scales written
    assert counts.qint8_pack_bytes(300, 128) == 1200 + 300 + 12
    assert counts.qint8_unpack_bytes(300, 128) == 1512


@pytest.mark.parametrize("name,expect", [("qwen2-vl-2b", 373762560),
                                         ("hymba-1.5b", 346539200)])
def test_param_count_of_the_configurations(name, expect):
    # the compiler's own count of one learner's parameters at this depth
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert counts.param_count(cfg) == expect


def test_a_family_is_found_by_name():
    from chipbench.families import dense, hybrid
    assert counts.param_count(TINY) == \
        2 * dense.layer_params(TINY) + 128 * 8 + 8
    hy = dict(TINY, family="hybrid", ssm_state=4, ssm_expand=2)
    assert hybrid.layer_params(hy) > dense.layer_params(TINY)
    with pytest.raises(KeyError):
        counts.forward_flops(dict(TINY, family="no-such-family"), 4)


def test_peaks_are_keyed_by_device_kind():
    v5e = device.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks_for("TPU v99")
