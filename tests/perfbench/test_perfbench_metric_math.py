"""perfbench.metric_math on hand-worked cases."""

import math

import pytest

from perfbench import metric_math as mm

MISTRAL_7B = {"hidden_size": 4096, "intermediate_size": 14336,
              "num_hidden_layers": 32, "num_attention_heads": 32,
              "num_key_value_heads": 8, "vocab_size": 32768}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 50, 2.5),
    ([4, 1, 3, 2], 25, 1.75),
    ([10], 90, 10),
    ([1, 2, 3, 4, 5], 90, 4.6),
    ([1, 2, math.inf], 50, 2),
    ([1, math.inf, math.inf], 50, math.inf),
    ([1, 2, 3, math.inf], 50, 2.5),
    ([1, 2, math.inf], 75, math.inf),
])
def test_percentile(values, q, want):
    assert mm.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        mm.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    assert mm.spread([100, 101, 102, 103, 104]) == pytest.approx(2 / 102)


def test_matmul_params_of_mistral_7b():
    # 7,248,023,552 parameters in all; less the embedding table (a gather)
    # and the 65 norm vectors
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert mm.matmul_params(MISTRAL_7B) == 32 * per_layer + 4096 * 32768
    assert mm.matmul_params(MISTRAL_7B) == \
        7_248_023_552 - 32768 * 4096 - 65 * 4096


def test_train_flops_per_token_is_bench_py_formula():
    cut = dict(MISTRAL_7B, num_hidden_layers=4, vocab_size=8192)
    n = 4 * 218_103_808 + 4096 * 8192
    assert mm.train_flops_per_token(cut, 4096) == \
        6.0 * n + 6.0 * 4 * 4096 * 4096
    assert mm.train_flops_per_token(cut, 4096) == pytest.approx(5.84e9,
                                                                rel=5e-3)


def test_flash_attention_requirement_matches_the_per_token_term():
    b, h, s, d, layers = 4, 32, 4096, 128, 4
    per_step = layers * mm.flash_attention_train_flops(b, h, s, d)
    assert per_step == 6.0 * layers * s * (h * d) * (b * s)
    q = b * h * s * d * 2
    kv = b * 8 * s * d * 2
    assert mm.flash_attention_train_bytes(b, h, 8, s, d) == 6 * q + 6 * kv


def test_roofline_names_its_bound():
    r = mm.roofline_seconds(197e12, 819e9 / 2, V5E)
    assert r == {"seconds": 1.0, "bound": "compute"}
    r = mm.roofline_seconds(197e12 / 4, 819e9, V5E)
    assert r == {"seconds": 1.0, "bound": "memory"}


def test_peaks_table_and_unknown_device():
    row = mm.device_peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        mm.device_peaks("TPU v9")
