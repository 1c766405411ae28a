"""costs.py against bench.py's arithmetic for the full model, and against a
brute-force count of each pattern's mask."""

import numpy as np
import pytest

from benchmarks import costs

FULL = costs.load_config("dalle-d12-full")
SPARSE = costs.load_config("dalle-d12-sparse")


def brute_force_mask(cfg, kind):
    """The pattern written as the loops of its description."""
    tl, f, k = costs.text_len(cfg), cfg["image_fmap_size"], cfg["conv_kernel_size"] // 2
    n = costs.seq_len(cfg)
    m = np.zeros((n, n), dtype=bool)
    for i in range(n):
        for j in range(i + 1):
            if kind == "full" or j < tl:
                m[i, j] = True
                continue
            (ri, ci), (rj, cj) = divmod(i - tl, f), divmod(j - tl, f)
            if kind == "axial_row":
                m[i, j] = ri == rj
            elif kind == "axial_col":
                m[i, j] = ci == cj
            elif kind == "conv_like":
                m[i, j] = abs(ri - rj) <= k and abs(ci - cj) <= k
    return m


@pytest.mark.parametrize("kind", ["full", "axial_row", "axial_col", "conv_like"])
def test_mask_equals_brute_force_and_the_programs(kind):
    small = dict(SPARSE, text_seq_len=6, image_fmap_size=6)
    assert (costs.pattern_mask(small, kind) == brute_force_mask(small, kind)).all()
    from dalle_pytorch_tpu.ops import masks

    n = costs.seq_len(SPARSE)
    theirs = masks.pattern_mask(kind, costs.text_len(SPARSE), SPARSE["image_fmap_size"])[:n, :n]
    assert (costs.pattern_mask(SPARSE, kind) == theirs).all()


def test_full_model_against_bench_py():
    import bench

    b, n, d = 8, costs.seq_len(FULL), FULL["dim"]
    assert costs.layer_matmul_params(FULL) == 16 * d * d
    mine = costs.train_step_flops(FULL, b)
    blocks = 3 * 2 * b * n * FULL["depth"] * 16 * d * d
    # bench.py counts the head over every position x the whole vocabulary and
    # attention over n x n pairs; costs.py counts what the loss and the causal
    # mask require. Put those two back and the totals agree.
    vocab = costs.text_vocab(FULL) + FULL["num_image_tokens"]
    full_head = 3 * 2 * b * n * d * vocab
    square_attention = mine["attention"] * 2 * n / (n + 1)
    assert bench.model_flops_per_step(b) == pytest.approx(blocks + full_head + square_attention, rel=1e-12)
    split_head = 3 * 2 * b * d * (256 * 10256 + 1024 * 8192)
    assert mine["matmul"] == blocks + split_head
    assert mine["total"] == mine["matmul"] + mine["attention"]


def test_sparse_layers_count_only_unmasked_pairs():
    full = costs.attended_pairs(SPARSE, "full")
    assert full == 1280 * 1281 // 2
    for kind in ("axial_row", "axial_col", "conv_like"):
        assert 0.3 < costs.attended_pairs(SPARSE, kind) / full < 0.45
    assert costs.train_step_flops(SPARSE, 8)["attention"] < 0.6 * costs.train_step_flops(FULL, 8)["attention"]
    assert costs.layer_kinds(SPARSE) == ["full", "axial_row", "axial_col", "conv_like"] * 3


def test_decode_step_bytes_follow_the_recorded_frontiers():
    weights = (16 * 1024 * 1024 * 12 + 1024 * 8192) * 2
    assert costs.decode_step_bytes(FULL, 0) == weights
    per_position = 2 * 1024 * 12 * 2
    assert costs.decode_step_bytes(FULL, 64 * 769) - weights == 64 * 769 * per_position


def test_unknown_device_is_an_error():
    assert costs.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        costs.load_peaks("cpu")
