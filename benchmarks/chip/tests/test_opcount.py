"""Operations, parameter counts and the peak table."""

import json

import pytest

import opcount
from conftest import HERE


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["qwen2-0.5b", "smollm-360m"])
def test_param_count_equals_the_programs(name):
    from repro.configs import get_config
    from repro.models.model import Model

    cfg = config(name)
    assert opcount.param_count(cfg) == Model(
        get_config(cfg["program_arch"])).n_params()


def test_qwen2_param_count_is_the_published_size():
    # 494M parameters with the tied embedding counted once
    assert opcount.param_count(config("qwen2-0.5b")) == 494_032_768


def test_padding_does_no_useful_work():
    cfg = config("qwen2-0.5b")
    # a round of one 100-token prompt, padded to 32 rows of 128, counts
    # the one prompt; an empty decode step counts nothing
    one = opcount.prefill_ops(cfg, 100)
    assert one < opcount.prefill_ops(cfg, 128) < 32 * one
    assert opcount.decode_ops(cfg, 0, 0) == 0
    # a live row's work grows with its real context only
    assert opcount.decode_ops(cfg, 1, 101) - opcount.decode_ops(
        cfg, 1, 100) == opcount.attention_ops(cfg, 1)


def test_prefill_ops_match_hand_count():
    cfg = config("qwen2-0.5b")
    d, f, v, n, hq, dh = 896, 4864, 151936, 24, 14, 64
    per_layer = d * hq * dh + 2 * d * 2 * dh + hq * dh * d + 3 * d * f
    L = 10
    want = (2 * per_layer * n * L + 4 * hq * dh * (L * (L + 1) // 2) * n
            + 2 * d * v)
    assert opcount.prefill_ops(cfg, L) == want


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        opcount.peaks("TPU v99 imaginary")
    assert opcount.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
