"""The functions that count a step's operations and bytes, against shapes
worked by hand, and the table of peaks."""

import json
import os

import pytest

from chipbench import manifest, peaks
from chipbench.references import bert as ref_bert
from chipbench.references import dlrm as ref_dlrm


def _sizes(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_dlrm_flops_by_hand_at_two_tables():
    sizes = {"vocab_sizes": [10, 20], "embed_dim": 4, "top_hidden": [8]}
    # top MLP input: 1 interaction + 4 = 5; layers 5x8 and 8x1
    mlp = 2 * 5 * 8 + 2 * 8 * 1
    interact = 2 * 2 * 2 * 4
    assert ref_dlrm.train_flops_per_row(sizes) == 3 * (interact + mlp)
    assert ref_dlrm.param_count(sizes) == 30 * 4 + (5 * 8 + 8) + (8 * 1 + 1)


def test_dlrm_mlperf_counts():
    sizes = _sizes("dlrm-mlperf")
    assert sum(sizes["vocab_sizes"]) == sizes["table_rows"] == 2_912_607
    # 19 tables: 171 interactions + 128 = 299 inputs to the top MLP
    mlp = 2 * (299 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
    assert ref_dlrm.train_flops_per_row(sizes) == 3 * (2 * 19 * 19 * 128 + mlp)
    assert ref_dlrm.train_flops_per_row(sizes) == pytest.approx(12.3e6,
                                                               rel=0.01)
    params = ref_dlrm.param_count(sizes)
    assert params == 2_912_607 * 128 + (299 * 1024 + 1024) + (
        1024 * 1024 + 1024) + (1024 * 512 + 512) + (512 * 256 + 256) + 257
    # dense Adam: 28 bytes a parameter dominate one 2048-row step
    step = ref_dlrm.train_step_bytes(sizes, 2048)
    assert step == 28 * params + 3 * 4 * 2048 * 19 * 128
    assert 28 * params / step > 0.99
    least, bound = peaks.roofline_seconds(
        ref_dlrm.train_flops_per_row(sizes) * 2048, step, "TPU v5 lite")
    assert bound == "bytes" and least == pytest.approx(12.9e-3, rel=0.02)


def test_bert_base_counts():
    sizes = _sizes("bert-base-mlm")
    params = ref_bert.param_count(sizes)
    # BERT-base without segment embeddings and pooler, tied output: ~109 M
    assert params == pytest.approx(108.9e6, rel=0.01)
    h, f, s, v = 768, 3072, 512, 30522
    per_token = 12 * (2 * (3 * h * h + h * h + 2 * h * f) + 4 * s * h) \
        + 2 * h * v
    assert ref_bert.train_flops_per_row(sizes) == 3 * s * per_token
    # the usual 6 x parameters x tokens estimate is within 15 % of it
    assert ref_bert.train_flops_per_row(sizes) == pytest.approx(
        6 * params * s, rel=0.15)
    least, bound = peaks.roofline_seconds(
        ref_bert.train_flops_per_row(sizes) * 32,
        ref_bert.train_step_bytes(sizes, 32), "TPU v5 lite")
    assert bound == "flops"


def test_bert_flops_by_hand_at_one_tiny_layer():
    sizes = {"hidden_dim": 2, "ffn_dim": 3, "seq_len": 4, "num_layers": 1,
             "vocab_size": 5}
    per_token = 2 * (2 * 6 + 2 * 2 + 2 * 2 * 3) + 2 * 2 * 4 * 2 + 2 * 2 * 5
    assert ref_bert.train_flops_per_row(sizes) == 3 * 4 * per_token


def test_peaks_table_is_keyed_by_device_kind_and_has_no_default():
    v5e = peaks.peaks_of("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
    assert peaks.roofline_seconds(197e12, 1.0, "TPU v5 lite") == (
        pytest.approx(1.0), "flops")
    assert peaks.roofline_seconds(1.0, 819e9, "TPU v5 lite") == (
        pytest.approx(1.0), "bytes")
