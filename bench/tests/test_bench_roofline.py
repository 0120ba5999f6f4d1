"""The least-bytes count and the peak table."""

import pytest

from bench import peaks, roofline


def test_op_bytes():
    # one 8-byte latch word and one 1 KiB line, each read and written
    assert roofline.op_bytes(256) == 2 * (8 + 1024)
    assert roofline.least_bytes(256, 256) == 256 * 2064
    assert roofline.least_bytes(0, 256) == 0


def test_roofline_share():
    # 1e6 ops of 2064 B at 819 GB/s on one chip take 2.52 ms at least
    share = roofline.roofline_share(10**6, 256, 2.52e-3 * 2, 819e9, 1)
    assert share == pytest.approx(2064e6 / 819e9 / 5.04e-3)
    # four chips: four times the bandwidth, busy is the mean over chips
    four = roofline.roofline_share(10**6, 256, 2.52e-3 * 2, 819e9, 4)
    assert four == pytest.approx(share / 4)


def test_peaks_known_and_unknown():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
