"""The benchmark's work counts against hand computations."""

import bench_testroot  # noqa: F401
import pytest

from bench.ref import dense_gqa
from bench.work import fd2d, flash_decode_paged, lm

CFG = {"hidden_size": 2048, "intermediate_size": 8192,
       "num_attention_heads": 16, "num_key_value_heads": 8,
       "num_hidden_layers": 24, "vocab_size": 92544, "rms_norm_eps": 1e-5,
       "rope_theta": 1e6}


def test_dims_of_internlm2_1_8b():
    n = dense_gqa.dims(CFG)
    assert (n["hd"], n["vpad"]) == (128, 92672)
    # published parameter count 1.89 B: layers + embedding + head
    total = (n["L"] * (lm.layer_weights(n) + 2 * n["d"]) + n["d"]
             + 2 * n["v"] * n["d"])
    assert total == 1_889_110_016


def test_flash_decode_paged_bytes_and_flops():
    n = dense_gqa.dims(CFG)
    flops, nbytes = flash_decode_paged.work(n, [100, 300])
    # keys and values: 400 positions x 8 heads x 128 x 2 B, twice
    kv = 400 * 8 * 128 * 2 * 2
    qo = 2 * 2 * 16 * 128 * 2            # q and out, 2 sequences
    assert nbytes == kv + qo == 1_654_784
    assert flops == 4 * 16 * 128 * 400 == 3_276_800


def test_lm_flops_per_token():
    n = dense_gqa.dims(CFG)
    per_layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192
    assert lm.layer_weights(n) == per_layer == 62_914_560
    assert lm.decode_flops(n, 10) == 24 * (2 * per_layer + 4 * 16 * 128
                                           * 10) + 2 * 2048 * 92544
    # a 3-token prefill attends to 1 + 2 + 3 positions, and reads one row
    # of logits
    assert lm.prefill_flops(n, 3) == 24 * (2 * per_layer * 3 + 4 * 16 * 128
                                           * 6) + 2 * 2048 * 92544


def test_fd2d_work():
    flops, nbytes = fd2d.work(8192, 8192, 1)
    assert nbytes == 3 * 8192 * 8192 * 4 == 805_306_368
    assert flops == 8192 * 8192 * 15


@pytest.mark.parametrize("r,want", [(1, [1, -2, 1]),
                                    (2, [-1 / 12, 4 / 3, -5 / 2, 4 / 3,
                                         -1 / 12])])
def test_fd_reference_weights(r, want):
    from bench.ref import fd2d as ref

    assert ref.second_derivative_weights(r) == pytest.approx(want)
