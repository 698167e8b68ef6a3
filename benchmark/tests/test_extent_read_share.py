"""``extent_read_share``: the OSDs' ``op_r_extent`` over their ``op_r``
across the window; None on a program without the counter (the parent
of the ranged EC read) and on a window with no read."""

import pytest

from benchmark.run import ROOT, RunData, read_metric


def _run(before: dict, after: dict) -> RunData:
    return RunData(records=[], t0=0.0, t_end=1.0,
                   before={"perf": [{"osd.0": before}]},
                   after={"perf": [{"osd.0": after}]})


@pytest.mark.parametrize("before,after,want", [
    ({"op_r": 10, "op_r_extent": 2}, {"op_r": 30, "op_r_extent": 18}, 0.8),
    ({"op_r": 10, "op_r_extent": 0}, {"op_r": 30, "op_r_extent": 0}, 0.0),
    ({"op_r": 10}, {"op_r": 30}, None),
    ({"op_r": 10, "op_r_extent": 2}, {"op_r": 10, "op_r_extent": 2}, None),
], ids=["share", "none_ranged", "no_counter", "no_read"])
def test_reads_the_window_share(before, after, want):
    assert read_metric(ROOT, "extent_read_share", _run(before, after)) \
        == want
