"""The rank kernel's necessary work counted from logical shapes, against a
hand count, and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401

from harness import peaks, roofline  # noqa: E402


def test_rank_call_hand_count():
    # 64 queries, 200 gallery rows of 64 floats, 8 cameras, top-1
    ops, nbytes = roofline.rank_call(Q=64, G=200, D=64, mask_width=8, k=1,
                                     gallery_tags=2)
    assert ops == 2 * 64 * 200 * 64 + 64 * 200          # 1,651,200
    assert nbytes == (64 * 64 * 4 + 200 * 64 * 4          # embeddings
                      + 64 * 8 + 64 * 4                   # masks, q tags
                      + 200 * 4 * 2                       # row tags
                      + 64 * 1 * 8)                       # top-1 out
    assert nbytes == 70_464


def test_rank_call_is_the_logical_work_only():
    # the tile path's fused mask is read once per query; nothing scales
    # with the padded shapes or the fused-cell one-hot product
    ops, nbytes = roofline.rank_call(Q=256, G=4096, D=64,
                                     mask_width=130 * 64, k=1,
                                     gallery_tags=2)
    assert ops == 2 * 256 * 4096 * 64 + 256 * 4096
    assert nbytes == (256 * 64 * 4 + 4096 * 64 * 4 + 256 * 8320
                      + 256 * 4 + 4096 * 8 + 256 * 8)


def test_least_time_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = roofline.least_time(1_651_200, 70_464, p)
    assert bound == "bytes" and t == pytest.approx(70_464 / 819e9)
    t, bound = roofline.least_time(10 ** 15, 1, p)
    assert bound == "ops" and t == pytest.approx(10 ** 15 / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    assert "819" in peaks.peaks("TPU v5 lite")["source"]
