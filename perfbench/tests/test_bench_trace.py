"""The trace reduction on a recorded trace and on hand-made ones.

``testdata/mixtral-w1-prefill.xplane.pb`` is a profiler trace of a 5.26 s
window of the Mixtral w1 prefill traffic on one TPU v5e (174 KB): 25 Pallas
kernel calls, the host spans ``bench.window`` and ``engine.step``.
"""
import os
import types

import pytest

from perfbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                    "mixtral-w1-prefill.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(trace.load(DATA))


def test_recorded_window_busy_and_kernel_time(recorded):
    assert recorded.window_s == pytest.approx(5.255325512, abs=1e-9)
    assert recorded.busy_s == pytest.approx(1.35095114, abs=1e-9)
    assert recorded.kernel_calls == 25
    assert recorded.kernel_s == pytest.approx(1.318700464, abs=1e-9)
    idle = 1.0 - recorded.busy_s / recorded.window_s
    assert idle == pytest.approx(0.7429367340014923, abs=1e-12)


def test_recorded_breakdown_uses_short_names(recorded):
    names = [n for n, _ in recorded.device_ops]
    assert names[0] == "incrs_spmm_pipelined.1 f32[14336,1024]"
    assert "copy s32[16,14336,166]" in names
    assert all("{" not in n and "custom-call" not in n for n in names)
    assert len(recorded.device_ops) <= trace.TOP
    assert recorded.device_ops[0][1] == pytest.approx(1.214821384, abs=1e-9)


def test_recorded_idle_gaps_follow_host_spans(recorded):
    gaps = dict(recorded.idle_gaps)
    assert set(gaps) <= {"engine.step", "engine.submit", "client.idle",
                         "untraced"}
    idle_s = recorded.window_s - recorded.busy_s
    assert sum(gaps.values()) == pytest.approx(idle_s, rel=1e-9)
    assert max(gaps, key=gaps.get) == "engine.step"


def test_kernel_until_limits_kernel_time(recorded):
    pd = trace.load(DATA)
    early = trace.reduce(pd, kernel_until_s=1.0)
    assert 0 < early.kernel_calls < recorded.kernel_calls
    assert early.busy_s == pytest.approx(recorded.busy_s)


def test_short_name():
    assert trace.short_name(
        "%copy.1 = f32[4096,1024]{1,0:T(8,128)} copy(f32[4096,1024]{1,0})"
    ) == "copy.1 f32[4096,1024]"
    assert trace.short_name("plain") == "plain"


def _fake(device_events, host_events):
    ev = lambda n, s, d: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d)
    line = lambda n, es: types.SimpleNamespace(  # noqa: E731
        name=n, events=[ev(*e) for e in es])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0",
                              lines=[line("XLA Ops", device_events)]),
        types.SimpleNamespace(name="/host:CPU",
                              lines=[line("python3", host_events)])])


CUSTOM = ('%{} = f32[8,128]{{1,0}} custom-call(f32[8,128]{{1,0}} %a), '
          'custom_call_target="tpu_custom_call"')


def test_kernels_match_by_kind_not_name():
    pd = _fake([(CUSTOM.format("a_renamed_kernel"), 100, 300),
                (CUSTOM.format("another_kernel.7"), 500, 100),
                ("%fusion.3 = f32[8,128]{1,0} fusion(...)", 700, 100)],
               [("bench.window", 0, 1000), ("engine.step", 0, 1000)])
    s = trace.reduce(pd)
    assert s.kernel_calls == 2
    assert s.kernel_s == pytest.approx(400e-9)
    assert s.busy_s == pytest.approx(500e-9)
    assert dict(s.idle_gaps) == pytest.approx({"engine.step": 500e-9})


def test_overlapping_ops_count_once_and_clip_to_window():
    pd = _fake([("%a = f32[1]{0} add()", -100, 300),
                ("%b = f32[1]{0} add()", 100, 200),
                ("%c = f32[1]{0} add()", 900, 500)],
               [("bench.window", 0, 1000)])
    s = trace.reduce(pd)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.kernel_calls == 0 and s.kernel_s == 0
    assert dict(s.idle_gaps) == pytest.approx({"untraced": 600e-9})


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(_fake([("%a = f32[1]{0} add()", 0, 1)], []))
    empty = _fake([], [("bench.window", 0, 10)])
    with pytest.raises(ValueError, match="TPU"):
        trace.reduce(empty)
