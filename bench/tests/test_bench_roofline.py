"""Operation and byte counts against hand-computed values."""
import json

import pytest

from bench import roofline

SMALL = {"conv_layers": 1, "conv_nodes": 4, "dense_nodes": 8,
         "resolution": 8, "color": "gray"}


def test_cnn_flops_by_hand():
    # conv 8x8x1 -> 4, 3x3: 2*64*9*1*4 = 4608; pool to 4x4x4 = 64
    # dense 64 -> 8: 2*64*8 = 1024; output: 2*8 = 16
    assert roofline.cnn_flops(SMALL) == 4608 + 1024 + 16


def test_param_bytes_by_hand():
    # conv 3*3*1*4 + 4, dense 64*8 + 8, out 8 + 1
    assert roofline.param_bytes(SMALL) == 4 * (40 + 520 + 9)


def test_stage0_kernel_work_by_hand():
    ops, nbytes = roofline.stage0_kernel_work(2, 32, [16, 8], SMALL)
    # pooled levels 16 and 8: 2 x 3*32*32 adds; grey 5*8*8; the CNN
    per_image = 2 * 3 * 32 * 32 + 5 * 64 + (4608 + 1024 + 16)
    assert ops == 2 * per_image
    # base at 1 byte/channel, the logit and both levels at float32
    per_image_b = 3 * 32 * 32 + 4 + 4 * 3 * (16 * 16 + 8 * 8)
    assert nbytes == 2 * per_image_b + roofline.param_bytes(SMALL)


def test_roofline_share_and_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = roofline.roofline_share(100.0, 50.0, 10.0, peak)
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = roofline.roofline_share(1000.0, 5.0, 20.0, peak)
    assert (share, bound) == (pytest.approx(50.0), "compute")


def test_peaks_known_and_unknown(tmp_path):
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"devices": {"A": {}}}))
    with pytest.raises(KeyError):
        roofline.peaks("cpu", table)
