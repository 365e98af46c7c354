"""`codec_roofline_pct` counts the encode+hash work and the reconstruct
work of one traced window together: a window in which PUTs encode and a
repair (or a degraded GET) reconstructs, as in `ec83-node-loss-mixed-c20`,
reads the least chip time of both kinds over the device's busy time.

`cell.reduce_trace` is run on the recorded trace (`data/small.xplane.pb`,
four executions on a TPU v5 lite) with the codec seam's counters of a
window that did both kinds of work."""

import os
import shutil
from types import SimpleNamespace

import pytest

from harness import cell, roofline, trace

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")
SEAM = "block_codec_blocks_total"
K, M, S = 8, 3, 131072


def window(tmp_path, encoded: float, reconstructed: float) -> dict:
    """What `_serve` hands `reduce_trace`: the trace's directory, the span on
    the profiler's clock (the whole recording), and the seam's counters."""
    tdir = tmp_path / "trace"
    (tdir / "plugins" / "profile" / "run").mkdir(parents=True)
    shutil.copy(SMALL, tdir / "plugins" / "profile" / "run" / "small.xplane.pb")

    def seam(op: str, path: str) -> tuple:
        return (SEAM, (("op", op), ("path", path)))

    before = {"counters": {seam("encode", "tpu"): 100.0, seam("reconstruct", "tpu"): 7.0}, "durations": {}}
    after = {"counters": {
        seam("encode", "tpu"): 100.0 + encoded,
        seam("reconstruct", "tpu"): 7.0 + reconstructed,
        # the host codec's blocks are no device work
        seam("encode", "numpy"): 50.0, seam("reconstruct", "numpy"): 9.0,
    }, "durations": {}}
    return {
        "t_trace_call": 0.0, "t_win": 0.0, "span": {"t_end": 0.2, "after": after},
        "tdir": str(tdir), "sampler": SimpleNamespace(samples=[]), "before": before,
        "cfg": {"k": K, "m": M}, "shapes": {"shard_bytes": S},
    }


def test_a_window_of_puts_and_repairs_counts_both_kinds(tmp_path):
    device = {"platform": "tpu", "kind": "TPU v5 lite"}
    peaks = roofline.peaks_for(device["kind"])
    busy = trace.reduce_planes(trace.read_xplane(SMALL), 0.0, 0.2e9)["busy_s"]

    both = cell.reduce_trace(window(tmp_path / "both", 40, 128), device)
    least, roof = roofline.codec_least_seconds(40, 128, K, M, S, peaks)
    assert (both["codec_least_s"], both["roof"]) == (pytest.approx(least), roof)
    assert both["codec_roofline_pct"] == pytest.approx(100.0 * least / busy)

    # each kind alone reads less; together they read the sum of the two
    enc = cell.reduce_trace(window(tmp_path / "enc", 40, 0), device)
    rec = cell.reduce_trace(window(tmp_path / "rec", 0, 128), device)
    assert 0 < enc["codec_least_s"] < both["codec_least_s"]
    assert 0 < rec["codec_least_s"] < both["codec_least_s"]
    assert both["codec_least_s"] == pytest.approx(enc["codec_least_s"] + rec["codec_least_s"])
    assert both["codec_roofline_pct"] == pytest.approx(enc["codec_roofline_pct"] + rec["codec_roofline_pct"])
