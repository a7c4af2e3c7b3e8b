"""Run one benchmark cell: set-up, the measured window, metrics, and the
comparison that decides ``correct``.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the configuration's file of sizes (``configs/<config>.json``) and, beside
  it, its plain reference (``configs/<config>.py``: ``operand(cfg, key)``
  makes the dense float32 operand on the device from a key);
- the traffic mix (``traffic/<traffic>.json``), read by ``traffic.py``;
- each metric's reader (``metrics/<name>.py``, else ``metrics/<stem>.py``
  for a name ``<stem>.<suffix>``), a function ``read(run)`` that returns a
  number or None when it finds nothing to read.

The system under test is the program's normal serving path:
``sparse.plan_for_operand`` prepares the operand, ``serve.SpMMEngine``
packs and runs the waves, and each wave reaches ``kernels.ops.spmm``.
"""
from __future__ import annotations

import collections
import dataclasses
import faulthandler
import gc
import importlib.util
import json
import logging
import os
import resource
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check as _check
from . import trace as _trace
from . import traffic as _traffic
from . import work as _work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATE = os.path.join(ROOT, ".perfbench")      # traces, logs: git-ignored
CACHE = os.path.join(ROOT, ".jax_cache")      # fixed: the path is a key
DRAIN_S = 60.0
STALL_S = 0.5                                 # a longer turn is a stall
TRACE_JAX = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


# ----------------------------------------------------------------------
# Finding a cell's parts by name.
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    reference: object              # the configuration's plain reference
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_bench(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_bench(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = os.path.join(root, conf["file"])
    with open(cfg_path) as f:
        config = json.load(f)
    reference = load_module(os.path.splitext(cfg_path)[0] + ".py",
                            "perfbench_config_" + w["config"])
    with open(os.path.join(root, "perfbench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), w["config"], config, reference,
                w["traffic"], traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], root)


def load_reader(metric: str, root: str = ROOT) -> Callable:
    base = os.path.join(root, "perfbench", "metrics")
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(base, stem + ".py")
        if os.path.exists(path):
            return load_module(path, "perfbench_metric_" +
                               stem.replace(".", "_")).read
    raise FileNotFoundError(f"no reader for metric {metric!r} under {base}")


# ----------------------------------------------------------------------
# JAX: the chip, the compile cache, compile counts.
class Compiles:
    """Counts traces, compiles and persistent-cache loads as JAX reports
    them. One per process: ``Compiles.get()``."""
    _one: Optional["Compiles"] = None

    @classmethod
    def get(cls) -> "Compiles":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax
        self.n = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.n[name] += 1

    def _duration(self, name, _secs, **_):
        self.n[name] += 1

    def snapshot(self) -> dict:
        hits = self.n[CACHE_HIT]
        return {"traces": self.n[TRACE_JAX], "cache_loads": hits,
                "compiles": self.n[COMPILE] - hits,
                "programs": self.n[COMPILE]}


def init_jax(chips: int, require_chip: bool = True):
    """Refuse to run anywhere but on at least ``chips`` TPU chips, and keep
    JAX's persistent cache inside the checkout (a run off the chip, as the
    tests make, leaves JAX's cache settings alone)."""
    # libtpu writes its logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if not require_chip:
        return devices
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices


class _Picks(logging.Handler):
    """Collects the kernel variant that ``variant="auto"`` chose."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.lines: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if "picked" in msg:
            self.lines.append(msg)


_PICKS: Optional[_Picks] = None


def watch_picks() -> _Picks:
    global _PICKS
    if _PICKS is None:
        _PICKS = _Picks()
        log = logging.getLogger("repro.kernels.autotune")
        log.setLevel(logging.INFO)
        log.addHandler(_PICKS)
    return _PICKS


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------------
# Set-up: the operand, the engine, the right-hand-side pool, warm-up.
@dataclasses.dataclass
class System:
    engine: object
    op: _work.Operand
    pool: np.ndarray
    operand: Callable              # key -> dense operand on the device
    key_op: object
    times: Dict[str, float]


def operand_fn(cell: Cell) -> Callable:
    import jax
    ref, cfg = cell.reference, cell.config
    return jax.jit(lambda key: ref.operand(cfg, key))


def prepare(cell: Cell, seed: int, operand: Optional[Callable] = None):
    """Make the operand from the seed on the device and prepare it through
    the program's plan. Returns ``(bound plan, work.Operand, operand fn,
    operand key, right-hand-side key, seconds to synthesize, to prep)``."""
    import jax
    from repro.sparse import SparseSpec, api

    cfg = cell.config
    operand = operand or operand_fn(cell)
    key_op, key_rhs = jax.random.split(_check.seed_key(seed))
    t0 = time.perf_counter()
    a = np.asarray(operand(key_op))
    t1 = time.perf_counter()
    stored = cfg["stored"]
    op = _work.Operand(a.shape[0], a.shape[1], int(np.count_nonzero(a)),
                       np.dtype(stored["values"]).itemsize,
                       np.dtype(stored["indices"]).itemsize)
    bound = api.plan_for_operand(a, SparseSpec(cfg["format"]))
    return bound, op, operand, key_op, key_rhs, t1 - t0, \
        time.perf_counter() - t1


def serve(bound, op: _work.Operand, traffic: dict, key_rhs):
    """The program's engine over a prepared operand, and the seeded
    right-hand-side pool the mix's requests take their columns from."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import SpMMEngine
    engine = SpMMEngine(bound, max_wave_cols=int(traffic["max_wave_cols"]),
                        variant=traffic["variant"])
    pool = np.asarray(jax.jit(
        lambda key: jax.random.normal(key, (op.k, int(traffic["pool_cols"])),
                                      jnp.float32))(key_rhs))
    return engine, pool


def build(cell: Cell, seed: int) -> System:
    bound, op, operand, key_op, key_rhs, synth_s, prep_s = prepare(cell,
                                                                   seed)
    t0 = time.perf_counter()
    engine, pool = serve(bound, op, cell.traffic, key_rhs)
    return System(engine, op, pool, operand, key_op,
                  {"synth_s": synth_s + time.perf_counter() - t0,
                   "prep_s": prep_s})


def warm(system: System, traffic: dict) -> List[int]:
    """Serve one request of every wave width the mix can launch, so that
    every program the window runs is compiled or loaded now."""
    from repro.serve import engine as _engine
    widths = _traffic.wave_widths(traffic, _engine.WAVE_QUANTUM)
    t0 = time.perf_counter()
    for i, w in enumerate(widths):
        r = _engine.SpMMRequest(-1 - i, system.pool[:, :w])
        system.engine.submit(r)
        system.engine.run()
        if not r.done:
            raise RuntimeError(f"warm-up request of width {w} not served")
    system.times["warm_s"] = time.perf_counter() - t0
    return widths


# ----------------------------------------------------------------------
# The measured window.
@dataclasses.dataclass
class Window:
    seconds: float
    attempted: int = 0
    completed: int = 0
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    waves: List[tuple] = dataclasses.field(default_factory=list)
    cols: int = 0                  # columns retired inside the window
    stage_s: float = 0.0           # engine host prep during the window
    stage_waves: int = 0
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    wave_wall_s: List[float] = dataclasses.field(default_factory=list)
    max_wave_cols: int = 0
    in_window_programs: int = 0
    close_s: float = 0.0           # when the loop noticed the close
    turns: int = 0                 # turns of the window's loop
    stalls: List[dict] = dataclasses.field(default_factory=list)
    gc: Optional["GcWatch"] = None
    done_at_close: int = 0
    drain_s: float = 0.0

    def inside(self) -> List[int]:
        """Columns of each wave retired inside the window."""
        return [c for t, c in self.waves if t <= self.seconds]

    def kernel_until_s(self) -> float:
        """Seconds into the window of the last wave retired inside it: the
        kernels started by then are those of the window's waves (a wave is
        dispatched only after the one before it has retired)."""
        inside = [t for t, _ in self.waves if t <= self.seconds]
        return inside[-1] if inside else 0.0


class GcWatch:
    """Times the collector's passes while it is registered."""

    def __init__(self):
        self.n = collections.Counter()
        self.total_s = 0.0
        self.max_s = 0.0
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        d = time.perf_counter() - self._t0
        self.n[info["generation"]] += 1
        self.total_s += d
        self.max_s = max(self.max_s, d)


def _switches() -> int:
    return resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw


def run_window(system: System, traffic: dict, seed: int, seconds: float,
               sampler: _check.Sampler, compiles: Optional[Compiles] = None
               ) -> Window:
    """Drive the engine for ``seconds`` under the mix, then let every
    request sent in the window finish (up to ``DRAIN_S`` past the close).
    Latency runs from when a request was due; a request sent late is
    still timed from its due time."""
    from repro.serve.engine import SpMMRequest
    eng, pool = system.engine, system.pool
    st = eng.stats
    win = Window(float(seconds), max_wave_cols=int(eng.max_wave_cols))
    open_loop = traffic["loop"] == "open"
    sched = _traffic.open_schedule(traffic, seed, seconds) if open_loop \
        else []
    clients = None if open_loop else _traffic.ClosedClients(traffic, seed)
    pending: list = []
    last = [st["waves"], st["cols"]]

    def submit(req, t_due):
        r = SpMMRequest(req.rid, pool[:, req.offset:req.offset + req.width])
        with annotate("engine.submit"):
            eng.submit(r)
        win.lateness_s.append(time.perf_counter() - t_due)
        win.attempted += 1
        pending.append((req, r, t_due))

    def settle(t_now, sending):
        if st["waves"] != last[0]:
            win.waves.append((t_now - t0, st["cols"] - last[1]))
            last[0], last[1] = st["waves"], st["cols"]
        done = [p for p in pending if p[1].done]
        if not done:
            return
        pending[:] = [p for p in pending if not p[1].done]
        for req, r, t_due in done:
            win.latencies_s.append(r.t_done - t_due)
            win.completed += 1
            sampler.offer((req.client + 1, req.rid), r.b, r.out)
            r.out = None          # the client has its answer
            if clients is not None and sending:
                submit(clients.next(req.client), time.perf_counter())

    gc.collect()
    gc.freeze()
    gcw = win.gc = GcWatch()
    gc.callbacks.append(gcw)
    os.makedirs(STATE, exist_ok=True)
    stall_log = open(os.path.join(STATE, "stalls.txt"), "w")
    prog0 = compiles.snapshot()["programs"] if compiles else 0
    prep0 = eng.stats_summary()["prep_s_total"]
    waves0, qw0, ww0 = st["waves"], len(eng._queue_wait_s), \
        len(eng._wave_wall_s)
    window = annotate("bench.window")
    window.__enter__()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    nxt = 0
    if clients is not None:
        for c in range(clients.n):
            submit(clients.next(c), t0)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        # A turn that passes STALL_S leaves its stacks in ``stall_log``
        # and its times in ``win.stalls``: which side held the loop.
        faulthandler.dump_traceback_later(STALL_S, file=stall_log)
        cpu0, gc0, sw0 = time.thread_time(), gcw.total_s, _switches()
        while nxt < len(sched) and t0 + sched[nxt].due_s <= now:
            submit(sched[nxt], t0 + sched[nxt].due_s)
            nxt += 1
        t_step = time.perf_counter()
        with annotate("engine.step"):
            did = eng.step()
        t = time.perf_counter()
        if did or not open_loop:
            settle(t, t < t_end)
        t_turn = time.perf_counter()
        faulthandler.cancel_dump_traceback_later()
        win.turns += 1
        if t_turn - now > STALL_S:
            win.stalls.append({
                "at_s": now - t0, "wall_ms": (t_turn - now) * 1e3,
                "submit_ms": (t_step - now) * 1e3,
                "step_ms": (t - t_step) * 1e3,
                "settle_ms": (t_turn - t) * 1e3,
                "cpu_ms": (time.thread_time() - cpu0) * 1e3,
                "gc_ms": (gcw.total_s - gc0) * 1e3,
                "preempted": _switches() - sw0})
        if open_loop and not did:
            due = t0 + sched[nxt].due_s if nxt < len(sched) else t_end
            with annotate("client.idle"):
                time.sleep(max(0.0, min(due, t_end) - time.perf_counter()))
    t_close = time.perf_counter()
    window.__exit__(None, None, None)
    gc.callbacks.remove(gcw)
    stall_log.close()
    win.close_s = t_close - t0
    win.done_at_close = win.completed
    win.stage_s = eng.stats_summary()["prep_s_total"] - prep0
    win.stage_waves = st["waves"] - waves0
    win.queue_wait_s = list(eng._queue_wait_s[qw0:])
    win.wave_wall_s = list(eng._wave_wall_s[ww0:])
    win.cols = sum(win.inside())
    win.in_window_programs = \
        (compiles.snapshot()["programs"] if compiles else 0) - prog0
    # Every request due in the window is sent, however late, and waited for.
    while nxt < len(sched):
        submit(sched[nxt], t0 + sched[nxt].due_s)
        nxt += 1
    deadline = t_close + DRAIN_S
    while pending and time.perf_counter() < deadline:
        did = eng.step()
        settle(time.perf_counter(), False)
        if not did:
            break
    win.drain_s = time.perf_counter() - t_close
    gc.unfreeze()
    return win


# ----------------------------------------------------------------------
# Metrics and the result line.
@dataclasses.dataclass
class Run:
    cell: Cell
    window: Window
    setup_s: float
    op: _work.Operand
    peak: Optional[_work.Peak]     # None only off the chip
    trace: Optional[_trace.Summary] = None


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = load_reader(m["name"], run.cell.root)(run)
        if value is None:
            print(f"metric {m['name']} found nothing to read",
                  file=sys.stderr)
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def compare(system: System, sampler: _check.Sampler, mode: str = "program"
            ) -> List[float]:
    """Gaps of the sampled requests against the plain reference, with the
    operand made anew from the seed."""
    a = system.operand(system.key_op)
    items = [v for _, v in sampler.items()]
    return _check.gaps(a, items, mode)


def checks(cell: Cell, win: Window, rel_gaps: List[float]) -> Dict[str, dict]:
    limit = float(cell.config["check"]["max_rel_err"])
    return {
        "max_rel_err": {"value": max(rel_gaps) if rel_gaps else None,
                        "limit": limit},
        "unfinished": {"value": win.attempted - win.completed, "limit": 0},
        "compared": {"value": len(rel_gaps), "limit": 1},
    }


def is_correct(cks: Dict[str, dict]) -> bool:
    err = cks["max_rel_err"]["value"]
    return (err is not None and err <= cks["max_rel_err"]["limit"]
            and cks["unfinished"]["value"] <= cks["unfinished"]["limit"]
            and cks["compared"]["value"] >= cks["compared"]["limit"])


def _q(xs, q):
    return float(np.quantile(np.asarray(xs), q)) if len(xs) else float("nan")


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_proc: float, require_chip: bool = True,
             root: str = ROOT) -> dict:
    """One run of one cell; returns the result object and prints the
    earlier lines on standard error."""
    err = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    cell = load_cell(name, root)
    devices = init_jax(cell.chips, require_chip)
    import jax
    compiles = Compiles.get()
    picks = watch_picks()
    t_jax = time.time() - t_proc
    system = build(cell, seed)
    warm(system, cell.traffic)
    setup = compiles.snapshot()
    sampler = _check.Sampler(seed, int(cell.traffic["compare"]))
    trace_dir = os.path.join(STATE, "trace", name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    setup_s = time.time() - t_proc
    if trace:
        jax.profiler.start_trace(trace_dir)
    win = run_window(system, cell.traffic, seed, seconds, sampler, compiles)
    if trace:
        jax.profiler.stop_trace()
    mem = memory_peak(devices[:max(cell.chips, 1)])
    kind = devices[0].device_kind
    run = Run(cell, win, setup_s, system.op,
              _work.peak_for(kind) if require_chip else None)
    if trace:
        run.trace = _trace.reduce(
            _trace.load(_trace.find_xplane(trace_dir)),
            kernel_until_s=win.kernel_until_s())
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)

    # The program's state goes before the reference runs.
    system.engine = None
    gc.collect()
    t_check = time.perf_counter()
    rel = compare(system, sampler)
    t_check = time.perf_counter() - t_check
    cks = checks(cell, win, rel)

    t = system.times
    err(f"setup setup_s={setup_s:.3f} jax_init_s={t_jax:.3f} "
        f"synth_s={t['synth_s']:.3f} prep_s={t['prep_s']:.3f} "
        f"warm_s={t['warm_s']:.3f} traces={setup['traces']} "
        f"compiles={setup['compiles']} cache_loads={setup['cache_loads']}")
    for line in sorted(set(picks.lines)):
        err(f"variant {line}")
    err(f"window seconds={seconds} closed_at_s={win.close_s:.4f} "
        f"attempted={win.attempted} completed={win.completed} "
        f"waves={len(win.inside())} cols={win.cols} "
        f"in_window_programs={win.in_window_programs} "
        f"drain_s={win.drain_s:.3f}")
    if win.lateness_s:
        err(f"generator late_ms p50={_q(win.lateness_s, .5) * 1e3:.3f} "
            f"p99={_q(win.lateness_s, .99) * 1e3:.3f} "
            f"max={max(win.lateness_s) * 1e3:.3f}")
    if win.queue_wait_s and win.wave_wall_s:
        err(f"engine queue_wait_ms p50={_q(win.queue_wait_s, .5) * 1e3:.3f} "
            f"p95={_q(win.queue_wait_s, .95) * 1e3:.3f} "
            f"wave_ms p50={_q(win.wave_wall_s, .5) * 1e3:.3f} "
            f"p95={_q(win.wave_wall_s, .95) * 1e3:.3f} "
            f"max={max(win.wave_wall_s) * 1e3:.3f}")
    err(f"loop turns={win.turns} stalls={len(win.stalls)} "
        f"gc_passes={dict(sorted(win.gc.n.items()))} "
        f"gc_max_ms={win.gc.max_s * 1e3:.3f} "
        f"gc_total_ms={win.gc.total_s * 1e3:.3f}")
    for st in win.stalls[:5]:
        err("stall " + " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                                else f"{k}={v}" for k, v in st.items()))
    if win.stalls:
        with open(os.path.join(STATE, "stalls.txt")) as f:
            err("stall stacks (first):\n" + "".join(f.readlines()[:40]))
    if win.latencies_s:
        err("latency_ms " + " ".join(
            f"p{int(q * 100)}={_q(win.latencies_s, q) * 1e3:.3f}"
            for q in (.1, .25, .5, .75, .9, .95, .99)))
    err(f"reference compared={len(rel)} seconds={t_check:.3f}")
    for k, v in cks.items():
        err(f"check {k}={v['value']} limit={v['limit']}")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": is_correct(cks), "attempted": win.attempted,
              "failed": win.attempted - win.completed, "metrics": metrics,
              "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checks"] = cks
    return result
