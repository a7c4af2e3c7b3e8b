"""The program's ``spmm.*`` spans as the benchmark reads them: innermost
attribution of idle time on hand-made and recorded traces, the per-layer
metrics that time one span on the tiny cells, the window's stage and
queue-wait numbers against the engine's record, and a stall placed by the
retire span it lay in."""
import os
import time

import jax
import numpy as np
import pytest

from perfbench import check, harness, spans, trace
from perfbench.tests import tiny
from perfbench.tests.test_bench_trace import DATA, _fake

SEED = 3000000001
NEW = ("retire_fetch_ms", "retire_copy_ms", "stage_put_ms")


@pytest.fixture(scope="module")
def root():
    return tiny.make(__import__("tempfile").mkdtemp())


# ----------------------------------------------------------------------
# Idle time by the innermost span.
def test_idle_goes_to_the_innermost_span():
    pd = _fake([("%a = f32[1]{0} add()", 0, 100)],
               [("bench.window", 0, 1000), ("engine.step", 0, 1000),
                ("spmm.retire.fetch", 200, 400)])
    window_s, busy_s, split = spans.idle_by_innermost(pd)
    assert dict(split) == pytest.approx({"spmm.retire.fetch": 400e-9,
                                         "engine.step": 500e-9})
    assert sum(dict(split).values()) == pytest.approx(window_s - busy_s)


def test_three_levels_and_untraced_time():
    pd = _fake([("%a = f32[1]{0} add()", 0, 100),
                ("%b = f32[1]{0} add()", 650, 20)],
               [("bench.window", 0, 1000), ("engine.step", 0, 900),
                ("spmm.retire", 150, 550), ("spmm.retire.fetch", 200, 400),
                ("spmm.retire.copy", 620, 80)])
    window_s, busy_s, split = spans.idle_by_innermost(pd)
    assert busy_s == pytest.approx(120e-9)
    assert dict(split) == pytest.approx({
        "engine.step": 50e-9 + 200e-9,        # [100,150) and [700,900)
        "spmm.retire": 50e-9 + 20e-9,         # [150,200) and [600,620)
        "spmm.retire.fetch": 400e-9,
        "spmm.retire.copy": 30e-9 + 30e-9,    # [620,650) and [670,700)
        "untraced": 100e-9})                  # [900,1000)
    assert sum(dict(split).values()) == pytest.approx(window_s - busy_s)
    assert [n for n, _ in split][0] == "spmm.retire.fetch"


def test_recorded_trace_without_engine_spans_splits_as_before():
    """A trace of a program without ``spmm.*`` spans splits its idle time
    as ``trace.reduce`` does: the harness's spans do not nest."""
    pd = trace.load(DATA)
    window_s, busy_s, split = spans.idle_by_innermost(pd)
    old = trace.reduce(pd)
    assert window_s == pytest.approx(old.window_s)
    assert busy_s == pytest.approx(old.busy_s)
    assert dict(split) == pytest.approx(dict(old.idle_gaps))
    assert spans.durations_s(spans.host_spans(pd), "spmm.retire.fetch") \
        == []


# ----------------------------------------------------------------------
# The per-layer metrics on the tiny cells, with a profiler trace.
def _events(path):
    """``[(name, start_ns, end_ns, wave)]`` of the ``spmm.*`` spans."""
    out = []
    for plane in trace.load(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)["wave"]))
    return out


@pytest.mark.parametrize("cell_name", ["tiny-open", "tiny-closed"])
def test_tiny_cells_read_the_span_metrics(root, cell_name, tmp_path,
                                          monkeypatch):
    monkeypatch.setattr(harness, "STATE", str(tmp_path))
    cell = harness.load_cell(cell_name, root)
    system = harness.build(cell, SEED)
    harness.warm(system, cell.traffic)
    eng = system.engine
    with jax.profiler.trace(spans.trace_dir(cell_name)):
        win = harness.run_window(system, cell.traffic, SEED, 0.8,
                                 check.Sampler(SEED, 2))
    summary = trace.Summary(win.seconds, 0.0, 0.0, 0, [], [])
    run = harness.Run(cell, win, 1.0, system.op, None, trace=summary)
    got = harness.read_metrics(run, cell.per_layer)
    want = {m["name"] for m in cell.per_layer
            if m["name"].split(".")[0] in NEW}
    kind = "latency" if cell_name == "tiny-open" else "throughput"
    assert want == {f"{n}.{kind}" for n in NEW
                    if kind == "throughput" or n != "stage_put_ms"}
    assert want <= set(got)
    # Each is the mean of the engine's own host timing of the same waves.
    path = trace.find_xplane(spans.trace_dir(cell_name))
    pd_spans = spans.host_spans(trace.load(path))
    w0, w1 = spans.window(pd_spans)
    recs = {w.seq: w for w in eng.trace.waves()}
    evs = _events(path)
    for name in want:
        span = {"retire_fetch_ms": "spmm.retire.fetch",
                "retire_copy_ms": "spmm.retire.copy",
                "stage_put_ms": "spmm.stage.put"}[name.split(".")[0]]
        field = span.rsplit(".", 1)[1] + "_s"
        waves = [wv for n, a, _, wv in evs if n == span and w0 <= a <= w1]
        assert waves
        host = np.mean([getattr(recs[wv], field) for wv in waves]) * 1e3
        assert got[name]["unit"] == "ms"
        assert got[name]["value"] == pytest.approx(host, rel=0.05, abs=0.05)
    # Without a trace the span metrics find nothing.
    run.trace = None
    assert not set(harness.read_metrics(run, cell.per_layer)) & want


def test_span_metrics_find_nothing_without_engine_spans(root, tmp_path,
                                                        monkeypatch):
    """On a program whose engine has no spans, as before them, a traced
    run leaves the three metrics out instead of failing."""
    monkeypatch.setattr(harness, "STATE", str(tmp_path))
    cell = harness.load_cell("tiny-closed", root)
    with jax.profiler.trace(spans.trace_dir(cell.name)):
        with harness.annotate("bench.window"):
            with harness.annotate("engine.step"):
                jax.numpy.ones(8).block_until_ready()
    summary = trace.Summary(1.0, 0.0, 0.0, 0, [], [])
    run = harness.Run(cell, harness.Window(1.0), 1.0, None, None,
                      trace=summary)
    for m in cell.per_layer:
        if m["name"].split(".")[0] in NEW:
            assert harness.load_reader(m["name"], root)(run) is None


# ----------------------------------------------------------------------
# The window's engine numbers against the engine's record.
def test_stage_and_queue_wait_follow_the_record(root, monkeypatch):
    """``stage_ms`` (staged host seconds over waves retired) and
    ``queue_wait_ms`` (median submit-to-dispatch wait) of a window equal
    their definitions computed from ``SpMMEngine.trace`` between the
    window's open and close."""
    from repro.serve.engine import ItemTrace, SpMMEngine, WaveTrace
    marks = []
    summary = SpMMEngine.stats_summary

    def spy(self):
        marks.append((self.trace.next_seq, self.stats["waves"]))
        return summary(self)
    cell = harness.load_cell("tiny-open", root)
    system = harness.build(cell, SEED)
    harness.warm(system, cell.traffic)
    monkeypatch.setattr(SpMMEngine, "stats_summary", spy)
    win = harness.run_window(system, cell.traffic, SEED, 1.0,
                             check.Sampler(SEED, 2))
    (s0, n0), (s1, n1) = marks
    entries = [e for e in system.engine.trace.since(s0) if e.seq < s1]
    waves = [e for e in entries if isinstance(e, WaveTrace)]
    waits = [e.queue_wait_s for e in entries if isinstance(e, ItemTrace)]
    assert waves and waits and n1 > n0
    run = harness.Run(cell, win, 1.0, system.op, None)
    assert harness.load_reader("stage_ms", root)(run) == pytest.approx(
        sum(w.stage_s for w in waves) / (n1 - n0) * 1e3, rel=1e-9)
    assert harness.load_reader("queue_wait_ms", root)(run) == \
        pytest.approx(float(np.median(waits)) * 1e3, rel=1e-12)


class _SlowFetch:
    """A wave's output whose device-to-host copy takes ``delay_s``."""

    def __init__(self, c, delay_s):
        self.c, self.delay_s = c, delay_s

    def block_until_ready(self):
        self.c.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.asarray(self.c, dtype=dtype)


def test_stall_in_the_fetch_is_placed_by_its_span(root, monkeypatch):
    """A slow device-to-host copy makes a stall of the window's loop, and
    the engine's record puts that wave's time in ``spmm.retire.fetch``,
    not in the wait for the device or in the panel copies."""
    from repro.serve.engine import SpMMEngine, WaveTrace
    delay = 1.5 * harness.STALL_S
    dispatch, calls = SpMMEngine._dispatch, [0]

    def slow(self):
        dispatch(self)
        calls[0] += 1
        if calls[0] == 3 and self._inflight is not None:
            self._inflight.c = _SlowFetch(self._inflight.c, delay)
    cell = harness.load_cell("tiny-closed", root)
    system = harness.build(cell, SEED)
    harness.warm(system, cell.traffic)
    seq0 = system.engine.trace.next_seq
    monkeypatch.setattr(SpMMEngine, "_dispatch", slow)
    win = harness.run_window(system, cell.traffic, SEED, 1.5,
                             check.Sampler(SEED, 2))
    assert len(win.stalls) == 1
    assert win.stalls[0]["step_ms"] >= delay * 1e3
    waves = [e for e in system.engine.trace.since(seq0)
             if isinstance(e, WaveTrace) and e.fetch_s is not None]
    slow_waves = [w for w in waves if w.fetch_s >= delay]
    assert len(slow_waves) == 1
    w = slow_waves[0]
    assert w.retire_s >= w.fetch_s
    assert w.wait_s < 0.5 * delay and w.copy_s < 0.5 * delay
    assert win.stalls[0]["step_ms"] >= w.fetch_s * 1e3
    assert os.path.exists(os.path.join(harness.STATE, "stalls.txt"))
