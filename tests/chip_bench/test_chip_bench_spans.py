"""``readers_spans``: the device's idle time cut by the program's own
``ds.*`` spans, on synthetic interval lists; and ``program_spans``
taking a file for this run's only by its ``bench.*`` events."""

import os

import pytest

import chip_bench_paths as paths  # noqa: F401  (puts the harness on the path)
import readers
import readers_spans

STEP, WAIT, ADMIT, STAGE = ("ds.sched.step", "ds.sched.device_wait",
                            "ds.sched.admit", "ds.engine.stage")


def trace(ops, bench=(("bench.sched_step", 0, 1000),)):
    return readers.Trace({d: [("op", s, e) for s, e in iv]
                          for d, iv in ops.items()}, list(bench))


# one device, window [0, 1000]: busy 100-400 and 600-900, so idle
# 0-100, 400-600 and 900-1000
ONE = {"/device:TPU:0": [(100, 400), (600, 900)]}
SPANS = [(STEP, 50, 950), (WAIT, 50, 450), (ADMIT, 450, 700),
         (STAGE, 500, 550)]


@pytest.mark.parametrize("kw,want", [
    # idle inside the wait: 50-100 and 400-450
    ({"inside": [WAIT]}, 10.0),
    # the gap 400-600 straddles two phases: admit holds 450-600 of it
    ({"inside": [ADMIT]}, 15.0),
    # ... of which the engine's staging is taken out
    ({"inside": [ADMIT], "minus": [STAGE]}, 10.0),
    ({"inside": [STAGE]}, 5.0),
    # outside the step: 0-50 and 950-1000
    ({"outside": [STEP]}, 10.0),
    # a name the trace does not hold covers nothing
    ({"inside": ["ds.sched.sweep"]}, 0.0),
    ({"outside": ["ds.train.loop"]}, 40.0),
    # the step's parts and its outside are the whole idle time
    ({"inside": [STEP]}, 30.0),
])
def test_idle_share_by_phase(kw, want):
    assert readers_spans.idle_share(trace(ONE), SPANS, **kw) == \
        pytest.approx(want)


def test_idle_share_is_the_mean_over_devices():
    two = dict(ONE, **{"/device:TPU:1": [(0, 1000)]})   # never idle
    assert readers_spans.idle_share(trace(two), SPANS, inside=[WAIT]) == \
        pytest.approx(5.0)
    # and the parts still add up to readers.idle_share of the same trace
    tr = trace(two)
    parts = readers_spans.idle_share(tr, SPANS, inside=[STEP]) + \
        readers_spans.idle_share(tr, SPANS, outside=[STEP])
    assert parts == pytest.approx(readers.idle_share({"trace": tr}))


def test_a_span_is_cut_to_the_window():
    spans = [(STEP, -500, 50), (STEP, 950, 5000)]
    assert readers_spans.idle_share(trace(ONE), spans, inside=[STEP]) == \
        pytest.approx(10.0)
    assert readers_spans.idle_share(trace(ONE), spans, outside=[STEP]) == \
        pytest.approx(30.0)


def test_nothing_to_read(monkeypatch):
    assert readers_spans.program_spans({"trace": None}) is None
    monkeypatch.setattr(readers_spans, "program_spans", lambda ctx: [])
    assert readers_spans.idle_share_in({"trace": trace(ONE)},
                                       inside=[WAIT]) is None
    monkeypatch.setattr(readers_spans, "program_spans", lambda ctx: None)
    assert readers_spans.idle_share_in({"trace": trace(ONE)},
                                       outside=[STEP]) is None
    monkeypatch.setattr(readers_spans, "program_spans", lambda ctx: SPANS)
    assert readers_spans.idle_share_in({"trace": trace(ONE)},
                                       inside=[WAIT]) == pytest.approx(10.0)
    # no device plane (a CPU run): nothing
    assert readers_spans.idle_share_in({"trace": trace({})},
                                       inside=[WAIT]) is None


def profile(tmp_path, cell, names):
    """A real ``.xplane.pb`` with the harness's options, laid out as
    run.py lays a traced run out: <root>/<cell>/plugins/profile/<t>/."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path / cell), profiler_options=opts)
    try:
        for name in names:
            with jax.profiler.TraceAnnotation(name):
                pass
    finally:
        jax.profiler.stop_trace()
    return readers.load_trace(str(tmp_path / cell))


def test_program_spans_reads_this_runs_file_and_no_other(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(readers_spans, "TRACE_ROOT", str(tmp_path))
    assert readers_spans.program_spans({"trace": trace(ONE)}) is None
    mine = profile(tmp_path, "cell-a",
                   ["bench.sched_step", "ds.sched.step", "ds.engine.launch",
                    "other"])
    assert [n for n, _, _ in mine.host_spans] == ["bench.sched_step"]
    got = readers_spans.program_spans({"trace": mine})
    assert sorted(n for n, _, _ in got) == ["ds.engine.launch",
                                            "ds.sched.step"]
    assert all(e > s > 0 for _, s, e in got)
    # the spans lie on the clock of the trace's own events
    (_, b0, b1), = mine.host_spans
    assert all(b1 <= s and e - b1 < 5e9 for _, s, e in got)
    # a context whose bench.* events are another run's is refused
    assert readers_spans.program_spans({"trace": trace(ONE)}) is None
    # a newer file of a program without the spans: nothing, and the older
    # run's context no longer finds its file
    parent = profile(tmp_path, "cell-b", ["bench.sched_step"])
    newest = max((os.path.getmtime(os.path.join(d, f)), d)
                 for d, _, fs in os.walk(tmp_path) for f in fs
                 if f.endswith(".xplane.pb"))[1]
    assert "cell-b" in newest
    assert readers_spans.program_spans({"trace": parent}) == []
    assert readers_spans.idle_share_in({"trace": parent},
                                       outside=[STEP]) is None
    assert readers_spans.program_spans({"trace": mine}) is None
