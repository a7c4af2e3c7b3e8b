"""The harness on the CPU at tiny sizes: cells found by name from new
files, the traffic generator, the comparison that decides ``correct`` and
its control, a whole run with the served path broken underneath, and the
refusals of ``run.py``."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from perfbench import calibrate, check, harness, traffic
from perfbench.tests import tiny

SEED = 3000000001          # past 31 bits: seeds may exceed an int32
CPU_TOL = 1e-5             # interpret mode computes in exact float32


@pytest.fixture(scope="module")
def root():
    return tiny.make(tempfile.mkdtemp())


def _digest(top, names):
    return {n: hashlib.sha256(open(os.path.join(top, n), "rb").read())
            .hexdigest() for n in names}


def _files(top):
    out = []
    for d, _, fs in os.walk(top):
        out += [os.path.relpath(os.path.join(d, f), top) for f in fs
                if "__pycache__" not in d and "testdata" not in d]
    return sorted(out)


# ----------------------------------------------------------------------
def test_new_config_traffic_and_metric_are_found_as_new_files(root):
    metric = "waves_seen.latency"
    with open(os.path.join(root, "perfbench", "metrics", "waves_seen.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.window.inside())\n")
    bench_path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    bench["per_layer"].append({
        "name": metric, "unit": "waves", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "p50_ms", "workloads": ["tiny-open"]})
    json.dump(bench, open(bench_path, "w"))
    # Every file the benchmark had is as it was in the repository.
    mine = os.path.join(tiny.REPO, "perfbench")
    before = [f for f in _files(mine)]
    assert _digest(mine, before) == _digest(os.path.join(root, "perfbench"),
                                            before)

    cell = harness.load_cell("tiny-open", root)
    assert cell.config_name == "tiny-pruned"
    assert cell.config["intermediate_size"] == 256
    assert cell.traffic["loop"] == "open"
    assert metric in [m["name"] for m in cell.per_layer]
    assert "cols_per_s" not in [m["name"] for m in cell.end_to_end]
    assert harness.load_reader(metric, root) is not None

    system = harness.build(cell, SEED)
    harness.warm(system, cell.traffic)
    win = harness.run_window(system, cell.traffic, SEED, 0.5,
                             check.Sampler(SEED, 2))
    run = harness.Run(cell, win, 1.0, system.op, None)
    got = harness.read_metrics(run, cell.per_layer)
    assert got[metric] == {"value": float(len(win.inside())),
                           "unit": "waves"}
    # Trace-read metrics find nothing without a trace and are left out.
    assert "idle_share.latency" not in got
    assert "wave_fill.latency" in got
    assert got["p95_ms.latency"]["value"] == pytest.approx(
        1e3 * float(np.quantile(win.latencies_s, 0.95)))


def test_real_cells_resolve():
    for name in ("mixtral-w1-decode", "mixtral-w1-prefill",
                 "docword-stream"):
        cell = harness.load_cell(name)
        for m in cell.end_to_end + cell.per_layer:
            assert harness.load_reader(m["name"]) is not None
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= {m["name"] for m in cell.end_to_end}


# ----------------------------------------------------------------------
def test_open_schedule_same_work_every_seed():
    """Every seed draws its own Poisson arrivals and widths from the same
    process: the same rate and width distribution, not the same draws."""
    mix = json.load(open(os.path.join(tiny.REPO, "perfbench", "traffic",
                                      "decode-poisson.json")))
    rate, secs = mix["rate_per_s"], 60.0
    a = traffic.open_schedule(mix, 1, secs)
    b = traffic.open_schedule(mix, SEED, secs)
    assert traffic.open_schedule(mix, SEED, secs) == b
    assert [r.due_s for r in a] != [r.due_s for r in b]
    for s in (a, b):
        n = rate * secs
        assert abs(len(s) - n) < 5 * np.sqrt(n)
        assert s[0].due_s == 0.0
        due = np.array([r.due_s for r in s])
        assert np.all(np.diff(due) > 0) and due[-1] < secs
        # Exponential gaps: the spread of a gap equals its mean.
        gaps = np.diff(due)
        assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)
        # Poisson counts per second: variance about the mean.
        per_s = np.bincount(due.astype(int), minlength=int(secs))
        assert per_s.var() / per_s.mean() == pytest.approx(1.0, abs=0.4)
        widths = np.array([r.width for r in s])
        assert widths.min() >= 8 and widths.max() <= 256
        assert 35 < widths.mean() < 55      # bounded Pareto, mean 45
        assert all(0 <= r.offset <= mix["pool_cols"] - r.width for r in s)


def test_closed_clients_go_round_the_list():
    mix = {"loop": "closed", "clients": 2, "pool_cols": 1024,
           "widths": {"dist": "list", "values": [128, 256, 512, 768]}}
    c = traffic.ClosedClients(mix, SEED)
    assert [c.next(0).width for _ in range(6)] == [128, 256, 512, 768,
                                                   128, 256]
    assert [c.next(1).width for _ in range(4)] == [512, 768, 128, 256]
    other = traffic.ClosedClients(mix, 7)
    reqs = [other.next(0) for _ in range(6)]
    assert [r.width for r in reqs] == [128, 256, 512, 768, 128, 256]
    assert [r.rid for r in reqs] == list(range(6))
    again = traffic.ClosedClients(mix, 7)
    assert [again.next(0) for _ in range(6)] == reqs


def test_wave_widths():
    one = {"loop": "closed", "clients": 1, "max_wave_cols": 1024,
           "widths": {"dist": "list", "values": [2048, 2560, 700]}}
    assert traffic.wave_widths(one, 128) == [512, 768, 1024]
    many = dict(one, clients=4)
    assert traffic.wave_widths(many, 128) == [768, 896, 1024]
    dec = {"loop": "open", "max_wave_cols": 512,
           "widths": {"dist": "bounded_pareto", "min": 8, "max": 256,
                      "alpha": 0.5}}
    assert traffic.wave_widths(dec, 128) == [128, 256, 384, 512]


# ----------------------------------------------------------------------
def test_gaps_of_exact_and_altered_answers():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 96)).astype(np.float32)
    bs = [rng.standard_normal((96, w)).astype(np.float32)
          for w in (5, 1200, 40)]
    outs = [(a.astype(np.float64) @ b).astype(np.float32) for b in bs]
    exact = check.gaps(jnp.asarray(a), list(zip(bs, outs)))
    assert max(exact) < CPU_TOL
    bad = [o.copy() for o in outs]
    bad[1][3, 1100] += 0.5 * np.abs(outs[1]).max()
    got = check.gaps(jnp.asarray(a), list(zip(bs, bad)))
    assert got[0] < CPU_TOL and got[2] < CPU_TOL
    assert got[1] == pytest.approx(0.5, rel=1e-4)


def test_control_is_not_correct(root):
    """The reference in fp8 put in the program's place is not correct by
    the run's own comparison, where the program is. bf16 (the chip's
    default precision, which the program reads exactly on the chip; see
    PERF.md) is reported beside it."""
    lines = []
    calibrate.calibrate(["tiny-open", "tiny-closed"], [SEED, 7], 1.0,
                        lines.append, require_chip=False, root=root)
    assert len(lines) == 4
    for line in lines:
        assert line["failed"] == 0 and line["compared"] >= 2
        assert line["program"] < CPU_TOL <= line["limit"]
        assert line["correct_program"] is True
        assert line["correct_fp8"] is False
        assert line["fp8"] > 2 * line["limit"]
        assert line["program"] < line["bf16"] < line["fp8"]
    # One checkpoint: the second seed reuses the prepared operand.
    assert [line["prep_s"] > 0 for line in lines] == [True, True, False, False]


def test_sampler_keeps_seeded_sample_and_widest():
    s = check.Sampler(SEED, 3)
    b = lambda w: np.zeros((2, w))  # noqa: E731
    for i, w in enumerate([4, 9, 2, 7, 3, 1]):
        s.offer((0, i), b(w), b(w))
    kept = dict(s.items())
    assert (0, 1) in kept                    # the widest
    assert 2 <= len(kept) <= 3
    t = check.Sampler(SEED, 3)
    for i, w in reversed(list(enumerate([4, 9, 2, 7, 3, 1]))):
        t.offer((0, i), b(w), b(w))
    assert sorted(dict(t.items())) == sorted(kept)


# ----------------------------------------------------------------------
def _run(root, cell="tiny-open"):
    return harness.run_cell(cell, SEED, 1.0, False, __import__("time").time(),
                            require_chip=False, root=root)


def test_whole_run_is_correct(root):
    res = _run(root)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_rel_err"]["value"] < CPU_TOL


def _altered(spmm):
    def broken(*a, **k):
        out = spmm(*a, **k)
        return out.at[0, :].add(0.1 * abs(out).max())
    return broken


def _half_left_out(finish):
    seen = [0]

    def broken(self, r, panel, t_done):
        seen[0] += 1
        if seen[0] % 2:
            panel = np.zeros_like(panel)
        return finish(self, r, panel, t_done)
    return broken


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_broken_served_path_is_not_correct(root, fault, monkeypatch):
    from repro.kernels import ops
    from repro.serve.engine import SpMMEngine
    if fault == "answer_altered":
        monkeypatch.setattr(ops, "spmm", _altered(ops.spmm))
    else:
        monkeypatch.setattr(SpMMEngine, "_finish_item",
                            _half_left_out(SpMMEngine._finish_item))
    res = _run(root)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


def test_stall_is_recorded_with_its_side(root, monkeypatch):
    """A turn of the window's loop that passes STALL_S is recorded with
    the time spent in the engine's step and its stacks are kept."""
    import time
    from repro.serve.engine import SpMMEngine
    step, calls = SpMMEngine.step, [0]

    def slow(self, *a, **k):
        calls[0] += 1
        if calls[0] == 3:
            time.sleep(1.5 * harness.STALL_S)
        return step(self, *a, **k)
    cell = harness.load_cell("tiny-closed", root)
    system = harness.build(cell, SEED)
    harness.warm(system, cell.traffic)
    monkeypatch.setattr(SpMMEngine, "step", slow)
    win = harness.run_window(system, cell.traffic, SEED, 1.5,
                             check.Sampler(SEED, 2))
    assert len(win.stalls) == 1
    st = win.stalls[0]
    assert st["step_ms"] >= 1.5 * harness.STALL_S * 1e3
    assert st["cpu_ms"] < 0.5 * st["step_ms"]      # asleep, not computing
    assert win.turns > 3
    stacks = open(os.path.join(harness.STATE, "stalls.txt")).read()
    assert "in slow" in stacks


# ----------------------------------------------------------------------
def test_run_refuses_a_machine_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(tiny.HERE, "run.py"), "--workload",
         "docword-stream", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=env, cwd=tiny.REPO)
    assert out.returncode == 3, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "no chip" in out.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copytree(tiny.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    code = ("import sys; sys.path.insert(0, '.'); "
            "from perfbench import run; "
            "sys.exit(run.main(['--workload', 'docword-stream', '--seed', "
            "'1', '--seconds', '1'], require_chip=False))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "repro" in out.stderr
