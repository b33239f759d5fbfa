"""The yardstick's arithmetic, checked against counts worked by hand."""

from __future__ import annotations

import pytest

from bench import counts, peaks


def test_jacobi_bytes_and_flops_by_hand():
    # a 6 x 6 float32 grid: read 36 cells, write the 4 x 4 interior
    assert counts.jacobi_bytes(6, 4) == 4 * (36 + 16) == 208
    # 3 additions and 1 multiplication per interior cell
    assert counts.jacobi_flops(6) == 64


def test_prefill_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 2, "intermediate_size": 16,
           "num_hidden_layers": 3, "vocab_size": 10}
    # q, k, v, o: 4 x 8 x 8 = 256; gate, up, down: 3 x 8 x 16 = 384
    assert counts.layer_params(cfg) == 640
    # per layer at s = 4: 2 x 640 x 4 + 2 x 16 x 8 = 5120 + 256;
    # three layers, then the head at one position: 2 x 8 x 10
    assert counts.prefill_flops(cfg, 4) == 3 * 5376 + 160


def test_deepseek_7b_stage_matches_the_published_sizes():
    cfg = {"hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 32, "intermediate_size": 11008,
           "num_hidden_layers": 4, "vocab_size": 102400}
    assert counts.layer_params(cfg) == 202_375_168
    assert round(counts.prefill_flops(cfg, 512) / 1e12, 2) == 0.84
    assert round(counts.prefill_flops(cfg, 2048) / 1e12, 2) == 3.45


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_byte_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
