"""Work counts and peaks against closed forms."""

import math

import pytest

from bench import counts, harness
from bench.peaks import peaks

FCN = harness.load_config("fcn-synthetic-4h")
LM = harness.load_config("smollm-135m")
FCN_DIMS = (FCN["input_dim"], *FCN["hidden"], FCN["output_dim"])


def test_fcn_parameters_match_the_paper():
    assert counts.fcn_params(FCN_DIMS) == FCN["params"] == 269_527_168


def test_fcn_step_is_six_per_weight_less_the_first_input_gradient():
    weights = sum(i * o for i, o in zip(FCN_DIMS[:-1], FCN_DIMS[1:]))
    per_sample = counts.total_flops(counts.fcn_train_gemms(FCN_DIMS, 1, 4))
    assert per_sample == 6 * weights - 2 * FCN_DIMS[0] * FCN_DIMS[1]
    assert 6 * weights == pytest.approx(6 * 269.5e6, rel=1e-3)


def test_smollm_parameters_and_kv_bytes():
    assert counts.lm_params(LM) == LM["params"] == 134_515_008
    assert counts.lm_matmul_params(LM) == 134_479_872
    assert counts.kv_bytes_per_token(LM) == 23_040


@pytest.mark.parametrize("seq", [128, 2048])
def test_smollm_train_flops_are_6n_plus_causal_attention(seq):
    n = counts.lm_matmul_params(LM)
    attn = 6 * seq * LM["n_heads"] * LM["d_head"] * LM["n_layers"]
    assert counts.lm_train_flops_per_token(LM, seq) == 6 * n + attn
    gemms = counts.lm_train_gemms(LM, 2, seq, 2)
    assert counts.total_flops(gemms) == pytest.approx(2 * seq * (6 * n + attn), rel=1e-12)


def test_decode_and_prefill_flops():
    n = counts.lm_matmul_params(LM)
    hd, L = LM["n_heads"] * LM["d_head"], LM["n_layers"]
    assert counts.lm_decode_flops(LM, 100) == 2 * n + 4 * 100 * hd * L
    head = LM["vocab"] * LM["d_model"]
    assert counts.lm_prefill_flops(LM, 10) == 2 * (n - head) * 10 + 2 * head + 2 * 100 * hd * L


def test_decode_bytes_count_weights_cache_and_new_token():
    got = counts.lm_decode_bytes(LM, [10, 0])
    assert got == LM["params"] * 2 + 23_040 * (10 + 2)


def test_roofline_takes_the_larger_bound():
    g = counts.gemm(1024, 1024, 1024, 2)
    assert counts.roofline_s([g], 1e12, 1e9) == pytest.approx(max(g.flops / 1e12, g.bytes / 1e9))
    assert g.flops == 2 * 1024 ** 3 and g.bytes == 2 * 3 * 1024 ** 2


def test_peaks_are_published_values_and_unknown_kinds_fail():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and "v5e" in p["source"]
    assert not any("f32" in k for k in p)
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    assert math.isfinite(p["bf16_flops_per_s"])
