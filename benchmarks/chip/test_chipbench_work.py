"""Work counts of the paged-decode kernel and the model step, and the peak
table, against hand counts."""
import json
import os

import pytest

from chipbench import peaks, work

HERE = os.path.dirname(os.path.abspath(__file__))


def _shape(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return work.Shape.from_config(json.load(f))


def test_paged_attention_counts_live_tokens_by_hand():
    s = _shape("qwen3-1.7b")     # 16 query heads, 8 KV heads, hd 128, bf16
    live = [1, 17, 300, 1536]    # ragged: one key, a page and one, ...
    flops, bytes_ = work.paged_attention_call(s, live)
    keys = 1 + 17 + 300 + 1536
    # QK^T and PV: 2 FLOPs per multiply-add, 16 heads x 128 each
    assert flops == 2 * 2 * 16 * 128 * keys
    # K and V rows of 8 heads x 128 x 2 bytes per live key, plus each
    # slot's q read and output written (16 x 128 x 2 bytes each)
    assert bytes_ == 2 * 8 * 128 * 2 * keys + 2 * 16 * 128 * 2 * 4
    # empty slots cost nothing: the count is of work, not of pages walked
    assert work.paged_attention_call(s, []) == (0.0, 0.0)


def test_least_time_takes_the_binding_peak():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_time(197e12, 819e9 / 2, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = work.least_time(1.0, 819e9 * 3, p)
    assert (t, bound) == (pytest.approx(3.0), "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes == 819e9


def test_model_flops_by_hand():
    s = _shape("mistral-nemo-12b-l10")
    d, hd, ff, v = 5120, 128, 14336, 131072
    per_layer = d * (32 + 2 * 8) * hd + 32 * hd * d + 3 * d * ff
    assert s.layer_matmul_params == per_layer
    # one decoded token attending 10 keys
    want = 10 * (2 * per_layer + 4 * 32 * hd * 10) + 2 * d * v
    assert work.decode_token_flops(s, 10) == want
    # a 3-token prefill after 5 cached tokens attends 6 + 7 + 8 keys
    want = 10 * (2 * per_layer * 3 + 4 * 32 * hd * 21) + 2 * d * v
    assert work.prefill_flops(s, 5, 3) == want
    assert work.model_flops(s, [[10], [10, 10]], [(5, 3)]) == \
        3 * work.decode_token_flops(s, 10) + work.prefill_flops(s, 5, 3)
