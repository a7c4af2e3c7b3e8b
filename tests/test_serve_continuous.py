"""Continuous-batching SpMM engine: cost-model wave packing (skip-scan
head-of-line fix, latency-budget targeting), oversized-request splitting,
prep/compute overlap accounting, mid-stream pattern swaps, stats_summary,
the engine's spans and its bounded trace record, the staging ring that
waves are packed into, and the multi-tenant LRU pool."""
import numpy as np
import pytest

from repro.core.incrs import InCRS
from repro.serve import scheduler as sched
from repro.serve.engine import SpMMEngine, SpMMRequest
from repro.serve.tenancy import TenantPool, operand_bytes


def _random_sparse(rng, m, k, density):
    d = rng.normal(size=(m, k)).astype(np.float32)
    d[rng.random(size=(m, k)) >= density] = 0.0
    return d


def _reqs(rng, k, widths):
    return [SpMMRequest(i, rng.normal(size=(k, w)).astype(np.float32))
            for i, w in enumerate(widths)]


def _check_outputs(done, d):
    for r in done:
        np.testing.assert_allclose(r.out, d @ r.b, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# Scheduler units: cost model + packer, no engine, no jax arrays needed.
class _Stub:
    def __init__(self, w):
        self.b = np.empty((1, w), np.float32)


def test_cost_model_fit_and_target():
    # Two measured points -> affine fit; target solves the budget back.
    slope, overhead = sched.fit_us_per_col([(100, 1100.0), (300, 3100.0)])
    assert slope == pytest.approx(10.0)
    assert overhead == pytest.approx(100.0)
    m = sched.WaveCostModel(us_per_col=10.0, launch_overhead_us=100.0)
    assert m.predict_us(50) == pytest.approx(600.0)
    assert m.target_cols(1100.0, hard_cap=512) == 100
    assert m.target_cols(1100.0, hard_cap=64) == 64     # cap always wins
    assert m.target_cols(None, hard_cap=512) == 512     # no budget
    assert m.target_cols(0.0, hard_cap=512) == sched.MIN_TARGET_COLS


def test_cost_model_ewma_converges():
    m = sched.WaveCostModel()
    assert m.predict_us(10) is None
    for _ in range(50):
        m.observe(100, 500.0)             # 5 µs/col, steady
    assert m.us_per_col == pytest.approx(5.0, rel=1e-3)
    assert m.n_observed == 50


def test_packer_skip_scan_fixes_head_of_line_blocking():
    """A wide head request must not starve narrower requests that fit in
    the same wave — the old FIFO stopped at the first non-fit."""
    from collections import deque
    q = deque([_Stub(100), _Stub(60), _Stub(20), _Stub(8)])
    barrier = sched.WavePacker(skip_limit=0)
    wave = barrier.next_wave(q, hard_cap=128)
    assert [r.b.shape[1] for r in wave] == [100]        # old behaviour
    q = deque([_Stub(100), _Stub(60), _Stub(20), _Stub(8)])
    packer = sched.WavePacker(skip_limit=8)
    wave = packer.next_wave(q, hard_cap=128)
    assert [r.b.shape[1] for r in wave] == [100, 20, 8]  # packed densely
    assert [r.b.shape[1] for r in q] == [60]             # order preserved


def test_packer_bypass_preserves_order_and_bound():
    from collections import deque
    widths = [90, 50, 50, 50, 30]
    q = deque(_Stub(w) for w in widths)
    packer = sched.WavePacker(skip_limit=1)              # bounded scan
    wave = packer.next_wave(q, hard_cap=100)
    # 90 admitted; 50 bypassed (1 skip allowed); scan stops at the bound.
    assert [r.b.shape[1] for r in wave] == [90]
    assert [r.b.shape[1] for r in q] == [50, 50, 50, 30]


def test_packer_budget_narrows_waves():
    from collections import deque
    cost = sched.WaveCostModel(us_per_col=10.0)
    packer = sched.WavePacker(cost=cost, budget_us=320.0)
    q = deque(_Stub(16) for _ in range(8))
    wave = packer.next_wave(q, hard_cap=512)
    assert sum(r.b.shape[1] for r in wave) <= 32         # 320µs / 10µs/col
    assert packer.last_target == 32


def test_seed_from_bench(tmp_path):
    import json
    path = tmp_path / "bench.json"
    path.write_text(json.dumps({"backend": "cpu", "interpret": True,
                                "rows": [
        {"name": "incrs_spmm_fused", "us": 6400.0, "derived": "cols=64"},
        {"name": "dense_mm_256", "us": 99.0, "derived": ""},
    ]}))
    m = sched.seed_from_bench(str(path), "interpret")
    assert m.us_per_col == pytest.approx(100.0)
    assert sched.seed_from_bench(str(tmp_path / "nope.json"),
                                 "interpret").us_per_col is None


def test_seed_from_bench_needs_matching_backend(tmp_path):
    """An interpreter record never seeds a chip engine's wave packing."""
    import json
    path = tmp_path / "bench.json"
    path.write_text(json.dumps({"backend": "cpu", "interpret": True,
                                "rows": [{"name": "incrs_spmm_fused",
                                          "us": 6400.0,
                                          "derived": "cols=64"}]}))
    assert sched.seed_from_bench(str(path), "interpret").us_per_col \
        == pytest.approx(100.0)
    assert sched.seed_from_bench(str(path), "tpu").us_per_col is None
    assert sched.seed_cost_model(backend="tpu", bench_path=str(path)) \
        .us_per_col is None


def test_seed_from_autotune_geometry_match(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "tune.json"))
    autotune.clear_memory_cache()
    cfg = autotune.TunedConfig("expand", 128, 128, 640.0, 500.0)
    autotune._store_disk(autotune.cache_key(128, 4, 7, 64, 64,
                                            "interpret"), cfg)
    m = sched.seed_from_autotune(128, 4, 7, 64, "interpret")
    assert m.us_per_col == pytest.approx(10.0)
    assert sched.seed_from_autotune(256, 4, 7, 64, "interpret") \
        .us_per_col is None                              # other geometry


# ----------------------------------------------------------------------
# Engine-level behaviour.
def test_engine_mixed_width_queue_packs_densely(rng):
    """Regression for the head-of-line fix at the engine level: the
    continuous engine serves a mixed-width queue in fewer waves than the
    wave-barrier baseline, with identical results."""
    d = _random_sparse(rng, 32, 400, 0.1)
    inc = InCRS.from_dense(d)
    widths = [100, 60, 20, 8, 100, 60, 20, 8]
    barrier = SpMMEngine(inc, max_wave_cols=128, continuous=False)
    for r in _reqs(rng, 400, widths):
        barrier.submit(r)
    done_b = barrier.run()
    cont = SpMMEngine(inc, max_wave_cols=128)
    for r in _reqs(rng, 400, widths):
        cont.submit(r)
    done_c = cont.run()
    assert cont.stats["waves"] < barrier.stats["waves"]
    assert len(done_c) == len(done_b) == len(widths)
    _check_outputs(done_b, d)
    _check_outputs(done_c, d)


def test_engine_oversized_request_split_across_waves(rng):
    """A request wider than max_wave_cols must not launch a kernel wider
    than the proven shape: it is split into parts and reassembled."""
    d = _random_sparse(rng, 24, 300, 0.1)
    inc = InCRS.from_dense(d)
    eng = SpMMEngine(inc, max_wave_cols=64)
    launched = []
    real_spmm = eng._ops.spmm

    def spy(prep, b, **kw):
        launched.append(b.shape[1])
        return real_spmm(prep, b, **kw)

    eng._ops = type("OpsSpy", (), {"spmm": staticmethod(spy),
                                   "INTERPRET": eng._ops.INTERPRET})()
    wide = SpMMRequest(0, rng.normal(size=(300, 150)).astype(np.float32))
    narrow = SpMMRequest(1, rng.normal(size=(300, 10)).astype(np.float32))
    eng.submit(wide)
    eng.submit(narrow)
    done = eng.run()
    # Every launch fits the proven cap up to lane bucketing: the engine
    # zero-pads waves to 128-col buckets, the same shape ops.spmm's
    # internal 128-multiple padding produces for any width <= the cap.
    from repro.serve.engine import WAVE_QUANTUM
    cap128 = -(-eng.max_wave_cols // WAVE_QUANTUM) * WAVE_QUANTUM
    assert all(w <= cap128 for w in launched)
    assert eng.stats["split_requests"] == 1
    assert eng.stats["split_parts"] == 3      # 64 + 64 + 22
    assert {r.rid for r in done} == {0, 1}
    assert wide.done and wide.out.shape == (24, 150)
    _check_outputs(done, d)


def test_engine_split_request_preserves_dtype(rng):
    d = _random_sparse(rng, 16, 200, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=32)
    b = rng.normal(size=(200, 70)).astype(np.float64)
    with pytest.warns(UserWarning, match="f32 precision"):
        eng.submit(SpMMRequest(0, b))
        done = eng.run()
    assert done[0].out.dtype == np.float64
    np.testing.assert_allclose(done[0].out.astype(np.float32),
                               (d @ b.astype(np.float32)),
                               rtol=1e-3, atol=1e-3)


def test_engine_prep_overlap_accounting(rng):
    """In continuous mode every wave after the first is prepped while the
    device computes — overlap fraction approaches (W-1)/W. The barrier
    mode hides nothing."""
    d = _random_sparse(rng, 16, 200, 0.1)
    inc = InCRS.from_dense(d)
    widths = [32] * 8                          # 8 waves at cap 32
    eng = SpMMEngine(inc, max_wave_cols=32)
    for r in _reqs(rng, 200, widths):
        eng.submit(r)
    eng.run()
    s = eng.stats_summary()
    assert s["waves"] == 8
    assert s["prep_s_total"] > 0
    assert s["prep_overlap_fraction"] >= 0.5   # 7 of 8 waves hidden
    barrier = SpMMEngine(inc, max_wave_cols=32, continuous=False)
    for r in _reqs(rng, 200, widths):
        barrier.submit(r)
    barrier.run()
    assert barrier.stats_summary()["prep_overlap_fraction"] == 0.0


def test_engine_stats_summary_shape(rng):
    d = _random_sparse(rng, 16, 200, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=64)
    for r in _reqs(rng, 200, [20, 20, 20]):
        eng.submit(r)
    eng.run()
    s = eng.stats_summary()
    assert s["mode"] == "continuous"
    assert s["requests"] == 3 and s["cols"] == 60
    assert s["requests_per_s"] > 0 and s["elapsed_s"] > 0
    for key in ("latency_ms", "queue_wait_ms", "wave_ms"):
        assert s[key]["p99"] >= s[key]["p50"] >= 0.0
    cm = s["cost_model"]
    assert cm["us_per_col"] is not None and cm["n_observed"] >= 1
    # The keys every reader of the summary uses, computed from the trace.
    assert set(s) == {"mode", "requests", "waves", "cols", "elapsed_s",
                      "requests_per_s", "latency_ms", "queue_wait_ms",
                      "wave_ms", "prep_s_total", "prep_s_hidden",
                      "prep_overlap_fraction", "stage_buf_allocs",
                      "stage_buf_reuses", "cost_model"}
    assert (s["stage_buf_allocs"], s["stage_buf_reuses"]) == (1, 0)
    assert set(cm) == {"us_per_col", "launch_overhead_us", "n_observed",
                       "source", "last_target_cols"}
    waves = eng.trace.waves()
    assert s["prep_s_total"] == pytest.approx(sum(w.stage_s for w in waves))
    lat = [e.latency_s for e in eng.trace.items()]
    assert s["latency_ms"]["p99"] == pytest.approx(max(lat) * 1e3)


# ----------------------------------------------------------------------
# The engine's trace: spans and counters of each wave, one bounded record.
SPANS = ("spmm.stage", "spmm.stage.pack", "spmm.stage.put", "spmm.dispatch",
         "spmm.retire", "spmm.retire.wait", "spmm.retire.fetch",
         "spmm.retire.copy")
CHILDREN = {"spmm.stage.pack": "spmm.stage", "spmm.stage.put": "spmm.stage",
            "spmm.retire.wait": "spmm.retire",
            "spmm.retire.fetch": "spmm.retire",
            "spmm.retire.copy": "spmm.retire"}


def test_engine_trace_counts_each_wave(rng):
    from repro.serve.engine import ItemTrace, WaveTrace
    d = _random_sparse(rng, 24, 300, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=64)
    wide = SpMMRequest(7, rng.normal(size=(300, 150)).astype(np.float32))
    narrow = SpMMRequest(8, rng.normal(size=(300, 10)).astype(np.float32))
    eng.submit(wide)
    eng.submit(narrow)
    done = eng.run()
    _check_outputs(done, d)
    entries = eng.trace.since(0)
    assert [e.seq for e in entries] == list(range(len(entries)))
    waves = [e for e in entries if isinstance(e, WaveTrace)]
    items = [e for e in entries if isinstance(e, ItemTrace)]
    assert len(waves) == eng.stats["waves"]
    assert sum(w.cols for w in waves) == eng.stats["cols"] == 160
    assert sum(w.pad_cols for w in waves) == eng.stats["pad_cols"]
    for w in waves:
        width = w.cols + w.pad_cols
        assert width % 128 == 0 and w.pad_cols < 128
        assert w.h2d_bytes == 300 * width * 4
        assert w.d2h_bytes == 24 * width * 4
        mine = [i for i in items if i.wave == w.seq]
        assert mine and w.seq < min(i.seq for i in mine)
        for part in ("stage", "pack", "put", "dispatch", "retire", "wait",
                     "fetch", "copy"):
            assert getattr(w, part + "_s") >= 0.0
        assert w.pack_s + w.put_s <= w.stage_s
        assert w.wait_s + w.fetch_s + w.copy_s <= w.retire_s
        assert w.wall_s >= w.fetch_s
    assert [w.hidden for w in waves] == [False] + [True] * (len(waves) - 1)
    # Three parts of the wide request, each with its parent's rid; the
    # latency lands on the part that completed it.
    parts = [i for i in items if i.part]
    assert [i.rid for i in parts] == [7, 7, 7]
    assert [i.latency_s is not None for i in parts] == [False, False, True]
    assert [i.rid for i in items if not i.part] == [8]
    assert all(i.queue_wait_s >= 0.0 for i in items)
    assert eng.stats_summary()["prep_s_hidden"] == pytest.approx(
        sum(w.stage_s for w in waves if w.hidden))
    assert len({i.wave for i in parts}) == 3


def test_engine_spans_reach_the_profiler(rng, tmp_path):
    """Under ``jax.profiler.trace`` each retired wave leaves exactly one of
    each span, with its ``wave`` and ``cols``: stage, then dispatch, then
    retire, each child inside its parent."""
    import glob
    import jax
    d = _random_sparse(rng, 16, 200, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=64)
    for r in _reqs(rng, 200, [40, 40, 100, 8]):
        eng.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        done = eng.run()
    _check_outputs(done, d)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    by_wave = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spmm."):
                    st = {k: v for k, v in e.stats}
                    by_wave.setdefault(st["wave"], []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         st["cols"]))
    waves = {w.seq: w for w in eng.trace.waves()}
    assert set(by_wave) == set(waves)
    assert sum(len(v) for v in by_wave.values()) == len(SPANS) * len(waves)
    for seq, evs in by_wave.items():
        assert sorted(n for n, *_ in evs) == sorted(SPANS)
        assert {c for *_, c in evs} == {waves[seq].cols}
        at = {n: (a, b) for n, a, b, _ in evs}
        assert at["spmm.stage"][1] <= at["spmm.dispatch"][0]
        assert at["spmm.dispatch"][1] <= at["spmm.retire"][0]
        for child, parent in CHILDREN.items():
            assert at[parent][0] <= at[child][0] <= at[child][1] \
                <= at[parent][1]


def test_engine_trace_is_a_bounded_ring(rng):
    from repro.serve.engine import TRACE_CAP, EngineTrace, ItemTrace
    ring = EngineTrace(cap=4)
    for i in range(10):
        ring.add(ItemTrace(i, 0, False, 0.0))
    assert [e.seq for e in ring.since(0)] == [6, 7, 8, 9]
    assert [e.rid for e in ring.since(8)] == [8, 9]
    assert ring.since(10) == [] and ring.next_seq == 10
    assert EngineTrace()._ring.maxlen == TRACE_CAP
    # An engine past its cap keeps serving and counting.
    d = _random_sparse(rng, 16, 200, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=32)
    eng.trace = EngineTrace(cap=8)
    reqs = _reqs(rng, 200, [32] * 6)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    _check_outputs(done, d)
    assert eng.trace.next_seq == 12            # six waves, six requests
    assert [e.seq for e in eng.trace.since(0)] == list(range(4, 12))
    s = eng.stats_summary()
    assert s["requests"] == 6 and s["waves"] == 6
    assert s["prep_s_total"] >= sum(w.stage_s for w in eng.trace.waves())


def test_engine_latency_budget_caps_wave_width(rng):
    d = _random_sparse(rng, 16, 200, 0.1)
    inc = InCRS.from_dense(d)
    cost = sched.WaveCostModel(us_per_col=10.0)
    packer = sched.WavePacker(cost=cost, budget_us=200.0, skip_limit=8)
    eng = SpMMEngine(inc, max_wave_cols=512, scheduler=packer)
    for r in _reqs(rng, 200, [10] * 6):
        eng.submit(r)
    done = eng.run()
    assert len(done) == 6
    # 200µs budget at >=10µs/col (EWMA may only raise it in interpret
    # mode) keeps waves at <=20 cols -> at least 3 waves, not one.
    assert eng.stats["waves"] >= 3
    _check_outputs(done, d)


def test_engine_step_retire_false_leaves_wave_in_flight(rng):
    d = _random_sparse(rng, 16, 200, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=32)
    for r in _reqs(rng, 200, [32, 32]):
        eng.submit(r)
    assert eng.step(retire=False)
    assert eng._inflight is not None and not eng.finished
    eng.run()
    assert len(eng.finished) == 2 and eng._inflight is None


# ----------------------------------------------------------------------
# The staging ring: every wave is packed into one of two reused host
# buffers of its dtype.
def _watch_staging(eng, monkeypatch):
    """Fail if a wave's host buffer changes between its put and its
    retire, the time a device transfer from it could still be reading it
    (on the CPU ``jnp.asarray`` copies at once, so a slot overwritten too
    early would not show in the outputs alone)."""
    import jax.numpy as jnp
    real = jnp.asarray
    sent = {}

    def asarray(x, *args, **kw):
        out = real(x, *args, **kw)
        if isinstance(x, np.ndarray):
            sent[id(out)] = (out, x, x.copy())
        return out

    monkeypatch.setattr(jnp, "asarray", asarray)
    retire = eng._retire

    def checked_retire():
        if eng._inflight is not None:
            _, view, snap = sent[id(eng._inflight.b)]
            np.testing.assert_array_equal(view, snap)
        retire()

    eng._retire = checked_retire
    return sent


def _ring_mix(rng, k):
    """At a 128-column cap: a full f32 wave, a 300-column f32 request split
    into 128 + 128 + 44, its last part with a bf16 and an f32 request (an
    f32 wave, padded), two bf16 requests (a bf16 wave, padded), and a
    100-column f32 request (a padded last bucket)."""
    import jax.numpy as jnp
    spec = [(128, np.float32), (300, np.float32), (40, jnp.bfloat16),
            (30, np.float32), (64, jnp.bfloat16), (50, jnp.bfloat16),
            (100, np.float32)]
    return [SpMMRequest(i, rng.normal(size=(k, w)).astype(dt))
            for i, (w, dt) in enumerate(spec)]


def _check_mixed_outputs(reqs, d):
    for r in reqs:
        assert r.done and r.out.dtype == r.b.dtype
        want = d @ r.b.astype(np.float32)
        tol = 1e-4 if r.b.dtype == np.float32 else 2e-2
        np.testing.assert_allclose(r.out.astype(np.float32), want,
                                   rtol=tol, atol=tol * np.abs(want).max())


def test_engine_staging_ring_serves_consecutive_waves(rng, monkeypatch):
    """Six consecutive waves through two slots a dtype: full, split,
    mixed bf16/f32, bf16 and padded waves all match the plain product, and
    no wave's buffer is written while the wave is in flight."""
    d = _random_sparse(rng, 24, 300, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=128)
    _watch_staging(eng, monkeypatch)
    reqs = _ring_mix(rng, 300)
    for r in reqs:
        eng.submit(r)
    eng.run()
    _check_mixed_outputs(reqs, d)
    waves = eng.trace.waves()
    assert [w.cols for w in waves] == [128, 128, 128, 114, 114, 100]
    assert [w.pad_cols for w in waves] == [0, 0, 0, 14, 14, 28]
    # Every wave is f32 but the fifth, which is bf16 alone.
    assert [w.h2d_bytes // (300 * 128) for w in waves] == [4, 4, 4, 4, 2, 4]
    assert eng.stats["split_parts"] == 3


def test_engine_staging_ring_across_a_swap(rng, monkeypatch):
    """``step(retire=False)``, then ``swap_pattern``, then more waves: the
    wave in flight keeps its operand and its buffer; later waves reuse the
    slots on the new operand."""
    d1 = _random_sparse(rng, 24, 300, 0.1)
    d2 = _random_sparse(np.random.default_rng(11), 24, 300, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d1), max_wave_cols=128)
    _watch_staging(eng, monkeypatch)
    reqs = _ring_mix(rng, 300)
    for r in reqs:
        eng.submit(r)
    assert eng.step(retire=False)          # wave 0 on d1; wave 1 staged
    assert eng._inflight is not None and eng._staged is not None
    eng.swap_pattern(InCRS.from_dense(d2))
    eng.run()
    _check_mixed_outputs(reqs[:1], d1)
    _check_mixed_outputs(reqs[1:], d2)
    more = _ring_mix(rng, 300)
    for r in more:
        eng.submit(r)
    eng.run()
    _check_mixed_outputs(more, d2)
    assert eng.stats["pattern_swaps"] == 1
    assert eng.stats["stage_buf_allocs"] == 2


@pytest.mark.parametrize("continuous", [True, False])
def test_engine_staging_ring_counters(rng, continuous):
    """One buffer pair a wave dtype, made on its first wave: allocations
    equal the distinct wave dtypes, reuses every other wave."""
    d = _random_sparse(rng, 24, 300, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=128,
                     continuous=continuous)
    for r in _ring_mix(rng, 300):
        eng.submit(r)
    eng.run()
    waves = eng.trace.waves()
    # f32 first, bf16 at its first bf16-only wave.
    first = {0, [w.h2d_bytes for w in waves].index(300 * 128 * 2)}
    assert [w.reused for w in waves] == [i not in first
                                         for i in range(len(waves))]
    s = eng.stats_summary()
    assert s["stage_buf_allocs"] == eng.stats["stage_buf_allocs"] == 2
    assert s["stage_buf_reuses"] == eng.stats["stage_buf_reuses"] \
        == s["waves"] - 2
    assert sorted(str(dt) for dt in eng._rings) == ["bfloat16", "float32"]
    for ring in eng._rings.values():
        assert [f.size for f in ring.flats] == [300 * 128] * 2


# ----------------------------------------------------------------------
# swap_pattern while requests are queued / in flight.
def test_swap_mid_stream_inflight_old_later_new(rng):
    """An in-flight wave finishes on the operand it was dispatched with;
    waves staged after the swap take the new one."""
    d1 = _random_sparse(rng, 24, 300, 0.1)
    d2 = _random_sparse(np.random.default_rng(7), 24, 300, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d1), max_wave_cols=32)
    reqs = _reqs(rng, 300, [32, 32, 32])
    for r in reqs:
        eng.submit(r)
    eng.step(retire=False)                 # wave 0 dispatched on d1
    eng.swap_pattern(InCRS.from_dense(d2))
    done = eng.run()
    assert len(done) == 3 and eng.stats["pattern_swaps"] == 1
    np.testing.assert_allclose(reqs[0].out, d1 @ reqs[0].b,
                               rtol=1e-4, atol=1e-4)
    for r in reqs[1:]:
        np.testing.assert_allclose(r.out, d2 @ r.b, rtol=1e-4, atol=1e-4)


def test_swap_rejected_mid_stream_leaves_queue_and_operand(rng):
    d = _random_sparse(rng, 24, 300, 0.1)
    eng = SpMMEngine(InCRS.from_dense(d), max_wave_cols=64)
    reqs = _reqs(rng, 300, [32, 32, 32])
    for r in reqs:
        eng.submit(r)
    old_prep = eng.prep
    wrong = InCRS.from_dense(_random_sparse(rng, 24, 200, 0.1))
    with pytest.raises(ValueError, match="shape"):
        eng.swap_pattern(wrong)            # shape mismatch -> rejected
    assert eng.prep is old_prep
    assert len(eng.queue) == 3 and eng.stats["pattern_swaps"] == 0
    done = eng.run()                       # still serves on the OLD operand
    assert len(done) == 3
    _check_outputs(done, d)


# ----------------------------------------------------------------------
# Multi-tenant pool.
def _make_inc(rng, m, k, density=0.1):
    d = _random_sparse(rng, m, k, density)
    return d, InCRS.from_dense(d)


def test_tenant_pool_serves_many_operands(rng):
    d1, inc1 = _make_inc(rng, 16, 200)
    d2, inc2 = _make_inc(rng, 32, 100)
    pool = TenantPool()
    pool.add("alpha", inc1, max_wave_cols=64)
    pool.add("beta", inc2, max_wave_cols=64)
    r1 = SpMMRequest(0, rng.normal(size=(200, 8)).astype(np.float32))
    r2 = SpMMRequest(1, rng.normal(size=(100, 8)).astype(np.float32))
    pool.submit("alpha", r1)
    pool.submit("beta", r2)
    served = pool.run()
    assert len(served) == 2 and r1.done and r2.done
    np.testing.assert_allclose(r1.out, d1 @ r1.b, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(r2.out, d2 @ r2.b, rtol=1e-4, atol=1e-4)
    s = pool.summary()
    assert s["n_resident"] == 2 and s["resident_bytes"] > 0


def test_tenant_pool_lru_eviction_and_revival(rng):
    d1, inc1 = _make_inc(rng, 64, 400)
    d2, inc2 = _make_inc(rng, 64, 400)
    pool = TenantPool(max_wave_cols=64)
    one = operand_bytes(pool.add("one", inc1).prep)
    pool.hbm_budget_bytes = int(one * 1.5)     # room for exactly one
    pool.add("two", inc2)
    assert not pool._tenants["one"].resident   # LRU evicted
    assert pool._tenants["two"].resident
    assert pool.stats["evictions"] == 1
    req = SpMMRequest(0, rng.normal(size=(400, 8)).astype(np.float32))
    pool.submit("one", req)                    # transparently revived
    pool.run("one")
    np.testing.assert_allclose(req.out, d1 @ req.b, rtol=1e-4, atol=1e-4)
    assert pool.stats["revivals"] == 1
    assert not pool._tenants["two"].resident   # budget held: two evicted
    assert len(pool.results("one")) == 1


def test_tenant_pool_never_evicts_busy_tenant(rng):
    _, inc1 = _make_inc(rng, 64, 400)
    _, inc2 = _make_inc(rng, 64, 400)
    pool = TenantPool(max_wave_cols=64)
    pool.add("one", inc1)
    pool.submit("one", SpMMRequest(
        0, rng.normal(size=(400, 8)).astype(np.float32)))
    pool.hbm_budget_bytes = 1                  # nothing fits
    pool.add("two", inc2)                      # "one" is busy: overcommit
    assert pool._tenants["one"].resident
    assert pool.stats["budget_overcommit"] >= 1
    with pytest.raises(ValueError, match="in-flight|queued"):
        pool.evict("one")
    pool.run("one")
    pool.evict("one")                          # drained: now evictable
    assert not pool._tenants["one"].resident


def test_tenant_pool_swap_survives_eviction(rng):
    """After a swap, an evict/revive cycle must rebuild the NEW operand,
    not the stale one the tenant was added with."""
    d1, inc1 = _make_inc(rng, 16, 200)
    d2, inc2 = _make_inc(np.random.default_rng(3), 16, 200)
    pool = TenantPool(max_wave_cols=64)
    pool.add("t", inc1)
    pool.swap_pattern("t", inc2)
    pool.evict("t")
    req = SpMMRequest(0, rng.normal(size=(200, 8)).astype(np.float32))
    pool.submit("t", req)                      # revive from retained a
    pool.run("t")
    np.testing.assert_allclose(req.out, d2 @ req.b, rtol=1e-4, atol=1e-4)


def test_tenant_pool_vmem_report(rng):
    _, inc = _make_inc(rng, 32, 200)
    pool = TenantPool(max_wave_cols=64)
    pool.add("t", inc)
    rep = pool.vmem_report()
    row = rep["tenants"]["t"]
    assert 0 < row["vmem_bytes"] <= rep["budget_bytes"]
    assert row["hbm_bytes"] == pool._tenants["t"].resident_bytes > 0
    with pytest.raises(KeyError):
        pool.submit("ghost", SpMMRequest(
            0, rng.normal(size=(200, 4)).astype(np.float32)))
