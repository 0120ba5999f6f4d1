"""The least-bytes count, the peak table, and the roofline reader."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import peaks, roofline, trace
from bench.metrics import reader

TRACE = Path(__file__).parent / "data" / "trace_v5e_small.json.gz"


def test_op_bytes():
    # one 8-byte latch word and one 1 KiB line, each read and written
    assert roofline.op_bytes(256) == 2 * (8 + 1024)
    assert roofline.least_bytes(256, 256) == 256 * 2064
    assert roofline.least_bytes(0, 256) == 0


def test_roofline_share():
    # 1e6 ops of 2064 B at 819 GB/s on one chip take 2.52 ms at least
    n_bytes = roofline.least_bytes(10**6, 256)
    share = roofline.roofline_share(n_bytes, 2.52e-3 * 2, 819e9, 1)
    assert share == pytest.approx(2064e6 / 819e9 / 5.04e-3)
    # four chips: four times the bandwidth, busy is the mean over chips
    four = roofline.roofline_share(n_bytes, 2.52e-3 * 2, 819e9, 4)
    assert four == pytest.approx(share / 4)


@pytest.mark.parametrize("n_ops, width, n_chips", [
    (2 * 256, 256, 1), (8 * 256, 256, 4), (7 * 256 - 3, 256, 1)])
def test_plane_roofline_reads_the_same_as_before(n_ops, width, n_chips):
    """On the recorded v5e trace, the reader's share from the driver's
    ``traced_bytes`` is, to the last bit, the one the harness gave when
    it priced ``traced_ops`` at ``2 * (8 + 4W)`` bytes itself."""
    reduced = trace.reduce(json.loads(gzip.decompress(TRACE.read_bytes())))
    busy, bw = reduced["busy_s"], peaks.peak("TPU v5 lite")[
        "hbm_bytes_per_s"]
    before = 100.0 * (int(n_ops) * 2 * (8 + 4 * width)
                      / (bw * n_chips) / busy)
    ctx = SimpleNamespace(trace=reduced, n_chips=n_chips,
                          peak=peaks.peak("TPU v5 lite"),
                          traced_bytes=roofline.least_bytes(n_ops, width))
    assert reader("plane_roofline")(ctx) == before
    # nothing served, or no device time: nothing to read
    assert reader("plane_roofline")(
        SimpleNamespace(**dict(vars(ctx), traced_bytes=0))) is None


def test_peaks_known_and_unknown():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
