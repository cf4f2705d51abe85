"""The trace reduction: interval union, idle gaps and their labels on
hand-made events, and the whole reduction on a small trace recorded on a
TPU v5e (``testdata/tiny.xplane.pb``: a 1024 x 1024 float32 matmul jitted
and run 3 times, a 10 ms sleep, once more, inside a host span named
``probe.window``)."""
from pathlib import Path

import pytest

from chipbench import traces

TINY = Path(__file__).resolve().parents[1] / "testdata" / "tiny.xplane.pb"


def test_union_merges_overlaps_and_clips_to_window():
    evs = [("a", 10, 10), ("b", 15, 10), ("c", 40, 5), ("d", 90, 30)]
    busy, gaps = traces.union(evs, 0, 100)
    assert busy == 15 + 5 + 10
    assert gaps == [(0, 10), (25, 40), (45, 90)]


def test_union_of_nothing_is_one_gap():
    assert traces.union([], 5, 9) == (0, [(5, 9)])


def test_gap_labels_pick_innermost_cover_then_most_overlap():
    spans = [("outer", 0, 100), ("inner", 20, 10), ("late", 200, 50)]
    assert traces.label_gap((22, 26), spans) == "inner"
    assert traces.label_gap((50, 60), spans) == "outer"
    assert traces.label_gap((150, 210), spans) == "late"
    assert traces.label_gap((300, 310), spans) == "(no host span)"


def test_short_op_names():
    assert traces.short_op("%fusion.3 = f32[]{:T(128)} fusion(f32[8] %x), "
                           "kind=kLoop") == "%fusion.3 fusion"
    assert traces.short_op("%copy-start = (f32[4]{0:S(1)}, u32[]{:S(2)}) "
                           "copy-start(f32[4] %a)") == "%copy-start copy-start"


@pytest.fixture(scope="module")
def tiny():
    return traces.load(str(TINY))


def test_recorded_trace_planes(tiny):
    assert traces.device_planes(tiny) == ["/device:TPU:0"]
    mods = tiny["/device:TPU:0"][traces.MODULES_LINE]
    assert len(mods) == 4 and all(m[0].startswith("jit__lambda") for m in mods)
    assert len(traces.host_events(tiny, ("probe.window",))) == 1


def test_recorded_trace_reduction(tiny):
    (_, lo, dur), = traces.host_events(tiny, ("probe.window",))
    r = traces.reduce(tiny, lo, lo + dur, [("probe.window", lo, dur)])
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(dur / 1e9)
    # four ~11.9 us matmuls at most; the rest of the 13.5 ms is idle
    assert 0 < r["busy_s"] <= 4 * 12e-6
    assert 0.99 < r["idle_share"] < 1.0
    assert r["device_ops"][0][0] == "%fusion fusion"
    longest = r["idle_gaps"][0]
    assert longest[0] == "probe.window"
    assert 0.009 < longest[1] < dur / 1e9       # the 10 ms sleep
    assert r["idle_gaps"] == sorted(r["idle_gaps"], key=lambda g: -g[1])
