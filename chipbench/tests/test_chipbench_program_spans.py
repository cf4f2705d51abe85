"""The program's context spans in a JAX profiler trace: a tiny ``train()``
with saves, run under the profiler, leaves its ``train.*`` and
``ckptmgr.*`` spans as host events, and the flight recorder's copies,
moved onto the trace's clock by one ``harness.annotate`` anchor (as
``run.read_trace`` does), agree with them to within 1 ms.  The per-layer
readers of the new spans and counters read them, and read nothing from a
program without them or from a run with no device trace."""
import jax
import pytest

from chipbench import harness, traces
from chipbench.tests.tiny import TINY
from repro.core import trace
from repro.distributed.sharding import make_variant
from repro.launch.mesh import make_local_mesh
from repro.train.loop import train

PREFIXES = ("train.", "ckptmgr.")
NEW_READERS = ("save.transfer_s", "save.host_copy_s", "save.final_wait_s",
               "loop.startup_s")
#: what ``traces.reduce`` gives a window with device planes (any non-empty)
DEVICE_TRACE = {"busy_s": 1.0, "window_s": 2.0, "idle_share": 0.5}


@pytest.fixture
def enabled():
    prev = trace.ENABLED
    trace.set_enabled(True)
    trace.clear()
    yield
    trace.set_enabled(prev)


def _by_name(events):
    out = {}
    for name, t0, dur in sorted(events, key=lambda e: e[1]):
        out.setdefault(name, []).append((t0, dur))
    return out


def _tiny_train(tmp_path):
    return train(harness.program_config(TINY), make_local_mesh(n=1),
                 make_variant("baseline"), n_steps=4, global_batch=4,
                 seq_len=32, log_every=1, seed=7, ckpt_root=tmp_path / "ck",
                 ckpt_every=2, keep=0)


def test_profiler_trace_holds_program_spans_on_its_clock(tmp_path, enabled):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    marks = []
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        with harness.annotate("test.window", marks):
            res = _tiny_train(tmp_path)
    finally:
        jax.profiler.stop_trace()
    assert res.steps_run == 4
    tr = traces.load(str(tmp_path / "prof"))
    host = _by_name(traces.host_events(tr, PREFIXES))
    assert {"train.startup", "train.step", "ckptmgr.snapshot",
            "ckptmgr.write"} <= set(host)
    # detached handles stay in the recorder only
    assert "ckptmgr.save" not in host

    (_, lo, _), = traces.host_events(tr, ("test.window",))
    shift = lo - int(marks[-1][1] * 1e9)
    recorded = _by_name(
        (e.name, int(e.t0 * 1e9) + shift, int(e.dur * 1e9))
        for e in trace.recorder().snapshot()
        if e.kind == "span" and e.name.startswith(PREFIXES)
        and e.name != "ckptmgr.save")
    assert set(recorded) == set(host)
    for name, spans in recorded.items():
        assert len(spans) == len(host[name]), name
        for (t0, dur), (h0, hdur) in zip(spans, host[name]):
            assert abs(t0 - h0) < 1e6, name
            assert abs((t0 + dur) - (h0 + hdur)) < 1e6, name


def test_new_readers_read_nothing_from_a_program_without_them():
    """The parent program has no snapshot parts and no loop spans: each
    new reader gives None there, and an evicted span reads the same."""
    run = harness.Run(cell=None, peaks={}, trace=DEVICE_TRACE)
    run.ckpt_stats = [{"saves": 8, "snapshot_s": 7.0, "write_s": 5.0}]
    run.program_spans = [("ckptmgr.snapshot", 1.0, 0.9),
                         ("ckptmgr.write", 1.9, 0.6)]
    for name in NEW_READERS:
        assert harness.load_reader(name)(run) is None, name
    run.ckpt_stats[0].update(snapshot_transfer_s=6.4, snapshot_copy_s=0.4)
    run.program_spans += [("train.startup", 0.0, 1.25),
                          ("train.final_wait", 30.0, 0.75)]
    assert [harness.load_reader(n)(run) for n in NEW_READERS] \
        == [0.8, 0.05, 0.75, 1.25]


def test_new_readers_read_a_tiny_train_with_saves(tmp_path, enabled):
    """On a real ``train()`` with saves every reader finds its counter or
    span; the snapshot's two parts fit inside it.  Without a device trace
    (``traces.reduce`` gives {} on the CPU) each leaves its metric out."""
    res = _tiny_train(tmp_path)
    run = harness.Run(cell=None, peaks={}, trace={})
    run.ckpt_stats = [dict(res.ckpt_stats)]
    run.program_spans = [(e.name, e.t0, e.dur)
                         for e in trace.recorder().snapshot()
                         if getattr(e, "dur", None) is not None]
    for name in NEW_READERS:
        assert harness.load_reader(name)(run) is None, name
    run.trace = DEVICE_TRACE
    got = {n: harness.load_reader(n)(run) for n in NEW_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["save.transfer_s"] + got["save.host_copy_s"] \
        <= harness.load_reader("save.snapshot_s")(run)
