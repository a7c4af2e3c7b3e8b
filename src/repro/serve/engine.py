"""Batched serving engine: wave-scheduled batching.

Requests are grouped into WAVES of equal prompt length (up to ``n_slots``
per wave); each wave is prefilled as one batch and decoded in lockstep with
a single jitted decode step. Wave batching keeps every cache's ring-buffer
arithmetic exact (all lanes share one position counter) — the trade-off vs.
slot-level continuous batching is a little admission latency, which the
paper's workload (batch SpMM-style inference) does not care about.

Works for every architecture family: attention KV rings, SSD states and
RG-LRU states all flow through ``model.decode_step`` opaquely.

``SpMMEngine``, further down, serves the paper's own workload: one sparse
operand against a stream of dense right-hand sides, packed into waves.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
import warnings
import weakref
from collections import defaultdict, deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model as M
from ..models.config import ModelConfig
from . import scheduler as _sched


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                     # (S,) int32
    max_new: int = 16
    temperature: float = 0.0               # 0 = greedy
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 alloc_extra: int = 64, cache_dtype=jnp.bfloat16,
                 seed: int = 0):
        self.cfg, self.params = cfg, params
        self.n_slots = n_slots
        self.alloc_extra = alloc_extra
        self.cache_dtype = cache_dtype
        self.rng = np.random.default_rng(seed)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.stats: Dict[str, int] = defaultdict(int)
        self._decode_jit = jax.jit(
            lambda p, tok, cache, pos: M.forward(
                cfg, p, tok, mode="decode", cache=cache,
                pos_offset=pos, remat=False),
            static_argnums=())

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)

    def _next_wave(self) -> List[Request]:
        """Pick up to n_slots queued requests sharing one prompt length."""
        if not self.queue:
            return []
        by_len: Dict[int, List[Request]] = defaultdict(list)
        for r in self.queue:
            by_len[len(r.prompt)].append(r)
        # largest group first (throughput)
        length = max(by_len, key=lambda k: len(by_len[k]))
        wave = by_len[length][: self.n_slots]
        for r in wave:
            self.queue.remove(r)
        return wave

    def _sample(self, logits_row: np.ndarray, temp: float) -> int:
        if temp <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row / temp
        z = z - z.max()
        prob = np.exp(z)
        prob /= prob.sum()
        return int(self.rng.choice(len(prob), p=prob))

    # ------------------------------------------------------------------
    def _run_wave(self, wave: List[Request]):
        cfg = self.cfg
        bsz = len(wave)
        s = len(wave[0].prompt)
        max_new = max(r.max_new for r in wave)
        prompts = jnp.asarray(np.stack([r.prompt for r in wave]))
        pfx = None
        npfx = cfg.n_prefix_embeds if cfg.input_mode == "embeds" else 0
        if cfg.input_mode == "embeds":
            # modality stub: deterministic zero frontend embeddings
            pfx = jnp.zeros((bsz, npfx, cfg.d_model), jnp.dtype(cfg.dtype))
        # The prefix embeddings occupy cache positions too: decode advances
        # to s + npfx + max_new - 1, so the allocation must cover npfx —
        # leaving it out overflows the KV ring whenever alloc_extra < npfx.
        logits, cache = M.prefill_step(
            cfg, self.params, prompts, prefix_embeds=pfx,
            alloc_seq=s + npfx + max_new + self.alloc_extra,
            cache_dtype=self.cache_dtype)
        self.stats["prefill_tokens"] += bsz * s
        lg = np.asarray(logits, dtype=np.float32)
        # Prefill sample only for lanes that actually want tokens: a
        # max_new=0 request must come back empty, and sampling for it would
        # consume shared-RNG draws that shift its wave-mates' outputs.
        last = np.zeros(bsz, dtype=np.int32)
        for i, r in enumerate(wave):
            if r.max_new > 0:
                last[i] = self._sample(lg[i], r.temperature)
                r.out.append(int(last[i]))
        for step in range(1, max_new):
            pos = s + npfx + step - 1
            logits, cache = self._decode_jit(
                self.params, jnp.asarray(last[:, None]), cache, pos)
            self.stats["decode_tokens"] += bsz
            lg = np.asarray(logits[:, -1], dtype=np.float32)
            for i, r in enumerate(wave):
                # Finished lanes are frozen: no sampling (shared-RNG
                # isolation) and ``last[i]`` stays put — the lockstep batch
                # still carries the lane, but nothing it produces is used.
                if len(r.out) < r.max_new:
                    tok = self._sample(lg[i], r.temperature)
                    r.out.append(tok)
                    last[i] = tok
        for r in wave:
            r.done = True
            self.finished.append(r)

    # ------------------------------------------------------------------
    def run(self) -> List[Request]:
        """Serve until the queue drains; returns finished requests."""
        while self.queue:
            wave = self._next_wave()
            self._run_wave(wave)
            self.stats["waves"] += 1
        return self.finished


# ----------------------------------------------------------------------
# The paper's OWN workload as a service: one fixed sparse operand A (InCRS),
# a queue of dense right-hand sides to multiply against it.
@dataclasses.dataclass
class SpMMRequest:
    rid: int
    b: np.ndarray                          # (K, cols) dense operand
    out: Optional[np.ndarray] = None       # (M, cols) result
    done: bool = False
    t_submit: Optional[float] = None       # stamped by engine.submit()
    t_done: Optional[float] = None         # stamped when the result lands


@dataclasses.dataclass
class _SplitPart:
    """One ``<= max_wave_cols``-wide column chunk of an oversized request.
    Parts flow through the packer like ordinary requests (they expose the
    same ``.b``); each retires into its parent's preallocated ``out``
    buffer, and the parent completes when its last part does."""
    rid: int
    parent: SpMMRequest
    offset: int                            # column offset into parent.out
    b: np.ndarray                          # column-slice VIEW of parent.b
    t_submit: Optional[float] = None


@dataclasses.dataclass
class WaveTrace:
    """One wave's entry in ``SpMMEngine.trace``: the host seconds of each of
    its spans and the counters taken at the same boundaries. Appended when
    the wave is staged; the dispatch and retire fields stay None until the
    wave gets there."""
    cols: int                              # request columns in the wave
    pad_cols: int                          # zero columns up to the bucket
    hidden: bool                           # staged while a wave computed
    h2d_bytes: int = 0                     # the concatenated RHS
    reused: bool = False                   # packed into a staging buffer
                                           # an earlier wave made
    stage_s: float = 0.0                   # spmm.stage
    pack_s: float = 0.0                    # spmm.stage.pack
    put_s: float = 0.0                     # spmm.stage.put
    dispatch_s: Optional[float] = None     # spmm.dispatch
    retire_s: Optional[float] = None       # spmm.retire
    wait_s: Optional[float] = None         # spmm.retire.wait
    fetch_s: Optional[float] = None        # spmm.retire.fetch
    copy_s: Optional[float] = None         # spmm.retire.copy
    d2h_bytes: Optional[int] = None        # the wave's output
    wall_s: Optional[float] = None         # dispatch -> result on host
    seq: int = -1


@dataclasses.dataclass
class ItemTrace:
    """One request's (or split part's) entry in ``SpMMEngine.trace``,
    appended when its wave is dispatched. A part carries its parent's
    ``rid``; ``latency_s`` is set on the item that completes its request."""
    rid: int
    wave: int                              # seq of the wave it rode
    part: bool
    queue_wait_s: Optional[float]          # submit -> dispatch
    latency_s: Optional[float] = None      # submit -> result on host
    seq: int = -1


# Entries the engine's trace keeps: a 51 s window of decode traffic adds
# about 10,000 (about 640 waves and 9,700 requests).
TRACE_CAP = 65536


class EngineTrace:
    """A ring of ``WaveTrace`` and ``ItemTrace`` entries with increasing
    sequence numbers: past ``cap`` the oldest entries drop, and the
    numbers keep counting. Staged host seconds are also summed over the
    engine's whole life."""

    def __init__(self, cap: int = TRACE_CAP):
        self._ring: Deque[Any] = deque(maxlen=cap)
        self.next_seq = 0
        self.stage_s_total = 0.0
        self.stage_s_hidden = 0.0

    def add(self, entry):
        entry.seq = self.next_seq
        self.next_seq += 1
        self._ring.append(entry)
        return entry

    def since(self, seq: int) -> List[Any]:
        """The entries numbered ``seq`` and later that the ring still
        holds, oldest first."""
        if not self._ring:
            return []
        skip = max(0, seq - self._ring[0].seq)
        return list(itertools.islice(self._ring, skip, None))

    def waves(self) -> List[WaveTrace]:
        return [e for e in self._ring if isinstance(e, WaveTrace)]

    def items(self) -> List[ItemTrace]:
        return [e for e in self._ring if isinstance(e, ItemTrace)]


class _Span:
    """A host span of one wave: a ``jax.profiler.TraceAnnotation`` (it
    lands in a profiler trace beside the device ops, with the stats
    ``wave`` and ``cols``) timed on the host clock into ``s``."""
    __slots__ = ("_ann", "t0", "s")

    def __init__(self, name: str, rec: WaveTrace):
        self._ann = jax.profiler.TraceAnnotation(name, wave=rec.seq,
                                                 cols=rec.cols)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)


@dataclasses.dataclass
class _Wave:
    """A packed wave moving through the stage -> dispatch -> retire
    pipeline. ``c`` is the dispatched device array (a future under JAX's
    async dispatch) once the wave is in flight; ``rec`` and ``entries``
    are its entries in the engine's trace (they hold no device array)."""
    items: List[Any]
    b: Any                                 # device-transferred concat RHS
    rec: WaveTrace
    c: Any = None
    t_dispatch: Optional[float] = None
    entries: List[ItemTrace] = dataclasses.field(default_factory=list)


class _StagingRing:
    """Two flat host buffers that the waves of one dtype are packed into in
    turn. Each is written once when made, so that its pages are faulted in
    then and not during a wave's copy. A slot remembers, weakly, the device
    array last put from it, and waits for that transfer before the slot is
    written again."""
    __slots__ = ("flats", "puts", "turn")

    def __init__(self, size: int, dtype):
        self.flats = [np.empty(size, dtype) for _ in range(2)]
        for flat in self.flats:
            flat.fill(0)
        self.puts: List[Optional[weakref.ref]] = [None, None]
        self.turn = 0

    def take(self, k: int, w: int) -> np.ndarray:
        """The next slot, as a C-contiguous ``(k, w)`` view."""
        i, self.turn = self.turn, self.turn ^ 1
        last = self.puts[i]() if self.puts[i] is not None else None
        if last is not None:
            jax.block_until_ready(last)
        return self.flats[i][:k * w].reshape(k, w)

    def sent(self, b) -> None:
        """Remember ``b``, the device array put from the slot last taken."""
        self.puts[self.turn ^ 1] = weakref.ref(b)


# Wave widths are bucketed (zero-padded) up to this quantum before launch
# — the TPU lane width, and the granularity the kernels pad to anyway.
WAVE_QUANTUM = 128


def _percentiles_ms(samples: List[float]) -> Dict[str, float]:
    """{p50, p99, mean} in milliseconds from wall-second samples."""
    if not samples:
        return {"p50": 0.0, "p99": 0.0, "mean": 0.0}
    srt = sorted(samples)

    def pct(q: float) -> float:
        return srt[min(len(srt) - 1, int(round(q * (len(srt) - 1))))] * 1e3

    return {"p50": pct(0.50), "p99": pct(0.99),
            "mean": sum(srt) / len(srt) * 1e3}


class SpMMEngine:
    """Continuous-batching SpMM serving on the fused InCRS kernel, single-
    or multi-device.

    The sparse operand is format-prepped exactly once (through the
    ``ops.prepare_incrs`` cache) at construction; every request wave reuses
    the ``PreparedOperand``, so steady-state serving cost is the fused
    kernel alone — no per-request host prep, no dense densification of A.

    Scheduling is cost-model-driven (``serve.scheduler``): wave width is
    chosen from measured µs/col (autotune cache / bench record, refined
    online per retired wave) against an optional per-wave
    ``latency_budget_us`` instead of always packing to one fixed size, and
    the queue is packed with a bounded skip-scan so a wide head request
    cannot starve narrower requests that fit. ``max_wave_cols`` remains
    the HARD cap — the shape the static feasibility check proves — and the
    budget may only narrow waves below it. Requests wider than the cap are
    split into parts across waves at ``submit()`` (each launch stays
    within the proven shape) and reassemble transparently.

    The engine pipelines host prep against device compute: while the
    device runs wave N (kernel calls return immediately under JAX's async
    dispatch; only the retiring ``np.asarray`` blocks), the host stages
    wave N+1, hiding the per-wave prep overhead the ``spmm_plan_vs_adhoc``
    bench measured. ``continuous=False`` restores the strict wave-barrier
    loop (FIFO, no skip-scan, no overlap) as the compatibility baseline
    ``serve_bench`` measures against.

    Staging packs each request's columns, cast to the wave's dtype, and the
    zero pad straight into a staging ring (``_StagingRing``): for each wave
    dtype, two flat host buffers of ``K * max_wave_cols`` (bucketed)
    elements, made and written once on the first wave of that dtype and
    used in turn after it. A fresh array a wave would fault its pages in
    during the copy. The ring costs ``2 * K * max_wave_cols * itemsize``
    bytes of host memory per dtype served (98 MB for the 12,000-row
    docword operand at 1,024 float32 columns) and lives as long as the
    engine. Reuse is safe because ``step()`` retires wave n, which waits
    for its output and so for its input's transfer, before it stages wave
    n+2, the next wave to write wave n's slot.

    With a ``mesh`` (or a pre-built ``ops.ShardedPreparedOperand``), the
    operand is row-sharded — one output-row stripe panel per mesh device —
    and each wave broadcasts its dense RHS to every device, runs the
    per-shard fused kernels under ``shard_map``, and concatenates the
    per-shard output panels. A is never gathered onto one device, so the
    servable operand scales with device count instead of one chip's VMEM.
    """

    def __init__(self, a, *, max_wave_cols: int = 512,
                 variant: str = "auto", interpret: Optional[bool] = None,
                 mesh=None, shard_axis=None, continuous: bool = True,
                 latency_budget_us: Optional[float] = None,
                 scheduler: Optional[_sched.WavePacker] = None,
                 skip_limit: Optional[int] = None):
        """``a``: an ``InCRS`` (prepped here, once, via the memo cache), an
        already-built ``ops.PreparedOperand`` /
        ``ops.ShardedPreparedOperand``, a ``sparse.Linear`` (its packed
        values serve zero-copy; any format), or a bound plan from the
        spec surface (``sparse.plan_for_operand(a, spec)`` /
        ``linear.bound()`` / ``plan.bind(values)``). Passing ``mesh``
        (with optional ``shard_axis``) row-shards a raw InCRS across that
        mesh at construction. ``variant`` selects the kernel grid order
        ("expand" | "reuse" | "pipelined" | "auto" — see ``ops.spmm``);
        "auto" rides a tuned config from the autotune cache when one
        exists for the wave shape, else the autotuner's cost model.

        ``continuous=False`` switches to the wave-barrier compatibility
        mode (strict FIFO, no prep/compute overlap). ``latency_budget_us``
        targets a per-wave latency through the cost model (continuous mode
        only). ``scheduler`` injects a pre-built ``scheduler.WavePacker``
        (overrides the budget/skip arguments); ``skip_limit`` bounds the
        head-of-line bypass scan (default ``scheduler.DEFAULT_SKIP_LIMIT``
        when continuous, 0 when not)."""
        from ..kernels import ops
        if variant not in ("auto", "expand", "reuse", "pipelined"):
            raise ValueError(f"variant must be 'auto', 'expand', 'reuse' "
                             f"or 'pipelined', got {variant!r}")
        self._ops = ops
        self.pattern_version: Optional[int] = None
        self.max_wave_cols = max_wave_cols
        self.variant = variant
        self._set_operand(a, mesh, shard_axis)
        self.interpret = interpret
        self.continuous = continuous
        if scheduler is None:
            if skip_limit is None:
                skip_limit = _sched.DEFAULT_SKIP_LIMIT if continuous else 0
            scheduler = _sched.WavePacker(
                cost=self._seed_cost_model() if continuous
                else _sched.WaveCostModel(),
                budget_us=latency_budget_us if continuous else None,
                skip_limit=skip_limit)
        self.scheduler = scheduler
        self.queue: Deque[Any] = deque()
        self.finished: List[SpMMRequest] = []
        self.stats: Dict[str, int] = defaultdict(int)
        self._staged: Optional[_Wave] = None
        self._inflight: Optional[_Wave] = None
        self._rings: Dict[np.dtype, _StagingRing] = {}
        self.trace = EngineTrace()
        self._t_first_submit: Optional[float] = None
        self._t_last_done: Optional[float] = None

    def _seed_cost_model(self) -> _sched.WaveCostModel:
        """Seed the packer's µs/col estimate from measurements this repo
        already persists for this engine's backend: the autotune disk
        cache for this operand's exact prepared geometry, else the
        committed bench record if it was measured on the same backend,
        else unseeded (the first retired wave provides the estimate)."""
        from ..kernels import autotune
        backend = autotune.backend_name(
            self._ops.resolve_interpret(self.interpret))
        geom = self._operand_geometry()
        if geom is None:
            return _sched.seed_cost_model(backend=backend,
                                          bench_path="BENCH_kernels.json")
        return _sched.seed_cost_model(
            padded_rows=geom[0], n_sections=geom[1], smax=geom[2],
            section=geom[3], backend=backend,
            bench_path="BENCH_kernels.json")

    def _operand_geometry(self):
        """(padded_rows, n_sections, smax, section) of the prepared InCRS
        stripes, or None when the operand has no fused-kernel geometry
        (e.g. a dense-format plan)."""
        from ..sparse import api
        prep = self.prep
        if isinstance(prep, api.BoundPlan):
            arrs = prep.plan._tuning_arrays()
            if arrs is None:
                return None
            idx, section = arrs
            return (int(idx.shape[1]), int(idx.shape[0]),
                    int(idx.shape[2]), int(section))
        idx = getattr(prep, "idx", None)
        if idx is None:
            return None
        if idx.ndim == 4:                  # sharded: per-device panel
            idx = idx[0]
        return (int(idx.shape[1]), int(idx.shape[0]), int(idx.shape[2]),
                int(prep.section))

    def _build_operand(self, a, mesh, shard_axis):
        """Resolve ``a`` to ``(operand, prep, pattern_version)`` WITHOUT
        touching engine state — every validation error leaves the engine
        exactly as it was (swap_pattern relies on this)."""
        ops = self._ops
        from ..sparse import api
        if isinstance(a, api.SparseSpec):
            raise ValueError(
                "a SparseSpec alone carries no values to serve — build an "
                "operand with sparse.plan_for_operand(a, spec) or pass a "
                "sparse.Linear")
        if isinstance(a, api.MatmulPlan):
            raise ValueError(
                "bind values to the plan first: plan.bind(values) (or "
                "pass a sparse.Linear / its .bound())")
        pattern = getattr(a, "pattern", None)       # lifecycle layer params
        if pattern is not None and hasattr(a, "prep"):
            a = a.prep                              # device-ready view
        if isinstance(a, api.Linear):
            a = a.bound()       # non-InCRS formats serve through the plan
        if isinstance(a, api.BoundPlan):
            if mesh is not None:
                raise ValueError(
                    "a bound plan is already committed to its layout — "
                    "rebuild it with a mesh on the spec instead of mesh=")
            return a, a, getattr(a.pattern, "version", None)
        if isinstance(a, ops.ShardedPreparedOperand):
            if mesh is not None and mesh is not a.mesh:
                raise ValueError(
                    "ShardedPreparedOperand is already bound to a mesh — "
                    "drop mesh=, or re-prep the raw InCRS on the new mesh")
            prep = a
        elif isinstance(a, ops.PreparedOperand):
            if mesh is not None:
                raise ValueError(
                    "cannot re-shard an already-built single-device "
                    "PreparedOperand — pass the raw InCRS with mesh=, or "
                    "an ops.ShardedPreparedOperand")
            prep = a
        elif mesh is not None:
            prep = ops.prepare_incrs_sharded(a, mesh, axis=shard_axis)
        else:
            prep = ops.prepare_incrs(a)
        return a, prep, getattr(pattern, "version", None)

    def _is_sharded(self, prep):
        from ..sparse import api
        if isinstance(prep, api.BoundPlan):
            return getattr(prep.plan.spec, "mesh", None) is not None
        return isinstance(prep, self._ops.ShardedPreparedOperand)

    def _check_feasible(self, prep) -> None:
        """Validate an incoming operand through the static kernel checker
        (``repro.analysis``) for this engine's wave shape, BEFORE it is
        committed: a tuned plan config is re-proven against the VMEM
        budgets, and an explicitly pinned variant must fit the hard
        per-core budget at ``max_wave_cols``. Raises
        ``analysis.KernelConfigError`` (a ValueError, so a rejected swap
        leaves the engine on the old operand)."""
        from ..analysis import kernel_check
        from ..sparse import api
        if isinstance(prep, api.BoundPlan):
            prep.plan.check_feasible(self.max_wave_cols)
            return
        if self.variant == "auto" or not hasattr(prep, "idx"):
            return            # auto dispatch only picks feasible orders
        idx = prep.idx
        if idx.ndim == 4:     # sharded: each device launches one panel
            idx = idx[0]
        # Same default col-tile heuristic ops.spmm applies at launch.
        np128 = -(-self.max_wave_cols // 128) * 128
        tiles = -(-np128 // 512)
        bn = -(-np128 // (tiles * 128)) * 128
        kernel_check.require_feasible(
            self.variant, m=idx.shape[1], n=self.max_wave_cols, bm=128,
            bn=bn, n_sections=idx.shape[0], smax=idx.shape[2],
            section=prep.section, rules=(kernel_check.RULE_VMEM,),
            context=f"engine variant={self.variant!r} at "
                    f"max_wave_cols={self.max_wave_cols}")

    def _set_operand(self, a, mesh, shard_axis):
        from ..sparse import api
        a, prep, version = self._build_operand(a, mesh, shard_axis)
        self._check_feasible(prep)
        self.a, self.prep, self.pattern_version = a, prep, version
        self._bound = self.prep if isinstance(self.prep, api.BoundPlan) \
            else None
        self.sharded = self._is_sharded(self.prep)

    # ------------------------------------------------------------------
    def swap_pattern(self, a, *, mesh=None, shard_axis=None) -> None:
        """Hot-swap the serving operand between waves — deploy a freshly
        re-pruned (or re-trained) pattern into the RUNNING engine without
        a restart. In plan–execute terms a swap IS a plan rebuild: the new
        operand arrives with its own static metadata, and the engine
        atomically starts executing against it.

        ``a`` accepts everything the constructor does — including a
        ``sparse.Linear`` of any format or a bound plan (their pattern
        version is recorded). The operand's global shape must match the
        current one: queued requests were validated against it, and a
        re-pruned layer keeps its logical shape by construction.
        Single-device and sharded operands can replace each other freely —
        waves after the swap simply take the other kernel path. A rejected
        swap (any ValueError) leaves the engine serving the OLD operand.
        """
        from ..sparse import api
        new_a, new_prep, new_version = self._build_operand(a, mesh,
                                                           shard_axis)
        self._check_feasible(new_prep)      # static VMEM proof pre-commit
        if tuple(new_prep.shape) != tuple(self.prep.shape):
            raise ValueError(
                f"swap_pattern: new operand shape {tuple(new_prep.shape)} "
                f"!= serving shape {tuple(self.prep.shape)} — an engine "
                f"serves one logical A; start a new engine for a new shape")
        self.a, self.prep, self.pattern_version = new_a, new_prep, \
            new_version
        self._bound = new_prep if isinstance(new_prep, api.BoundPlan) \
            else None
        self.sharded = self._is_sharded(new_prep)
        self.stats["pattern_swaps"] += 1

    def submit(self, req: SpMMRequest):
        k = self.a.shape[1]
        # A hard error, not an assert: shape validation must hold under
        # ``python -O`` too, or a mis-shaped RHS slips into a wave.
        if req.b.ndim != 2 or req.b.shape[0] != k:
            raise ValueError(
                f"request {req.rid}: b has shape {req.b.shape}, expected "
                f"({k}, cols) to multiply against A of shape {self.a.shape}")
        req.t_submit = time.perf_counter()
        if self._t_first_submit is None:
            self._t_first_submit = req.t_submit
        cols = req.b.shape[1]
        if cols > self.max_wave_cols:
            # Wider than the proven wave shape: split into parts that each
            # fit, instead of admitting a kernel launch the feasibility
            # check never proved. The parts reassemble into req.out.
            req.out = np.empty((self.prep.shape[0], cols),
                               dtype=req.b.dtype)
            n_parts = -(-cols // self.max_wave_cols)
            req._parts_left = n_parts
            for i in range(n_parts):
                lo = i * self.max_wave_cols
                hi = min(cols, lo + self.max_wave_cols)
                self.queue.append(_SplitPart(
                    rid=req.rid, parent=req, offset=lo,
                    b=req.b[:, lo:hi], t_submit=req.t_submit))
            self.stats["split_requests"] += 1
            self.stats["split_parts"] += n_parts
        else:
            self.queue.append(req)

    # -- pipeline stages ------------------------------------------------
    # Each stage runs in spans named ``spmm.<stage>[.<part>]`` (``_Span``);
    # their host seconds and the wave's counters go into ``self.trace``.
    def _staging_ring(self, dt: np.dtype, k: int,
                      rec: WaveTrace) -> _StagingRing:
        """The staging ring of wave dtype ``dt``, made on its first wave at
        the widest bucketed wave, ``max_wave_cols`` rounded up."""
        ring = self._rings.get(dt)
        rec.reused = ring is not None
        if rec.reused:
            self.stats["stage_buf_reuses"] += 1
            return ring
        cap = -(-self.max_wave_cols // WAVE_QUANTUM) * WAVE_QUANTUM
        ring = self._rings[dt] = _StagingRing(k * cap, dt)
        self.stats["stage_buf_allocs"] += 1
        return ring

    def _stage(self, hidden: bool) -> bool:
        """Pack the next wave off the queue and do ALL its host prep
        (dtype promotion, the pack into a staging buffer, device transfer).
        ``hidden`` says a dispatched wave is still computing, i.e. this
        prep overlaps the device and its cost is hidden from the serving
        critical path."""
        wave = self.scheduler.next_wave(self.queue, self.max_wave_cols)
        if not wave:
            return False
        cols = sum(r.b.shape[1] for r in wave)
        # Bucket the wave width to the lane quantum: packed widths are
        # data-dependent sums, and every DISTINCT width pays a one-time
        # trace/compile cost orders of magnitude above the launch itself.
        # Padding to the next 128-col bucket collapses all waves onto a
        # handful of kernel shapes (the kernel pads to 128-multiples
        # internally anyway, so the zero columns cost no extra compute).
        pad = -(-cols // WAVE_QUANTUM) * WAVE_QUANTUM - cols
        rec = self.trace.add(WaveTrace(cols, pad, hidden))
        with _Span("spmm.stage", rec) as stage:
            with _Span("spmm.stage.pack", rec) as pack:
                # Promote WITHIN the wave: a bf16 request sharing a wave
                # with f32 neighbours computes at f32, and every request's
                # panel comes back in ITS OWN dtype. The fused kernel
                # accumulates in f32 — that is the compute-precision
                # ceiling — so a wider-than-f32 wave (f64 requests) is
                # computed at f32 and says so instead of silently
                # relabeling f32 numbers as f64.
                wave_dt = functools.reduce(jnp.promote_types,
                                           (r.b.dtype for r in wave))
                if jnp.issubdtype(wave_dt, jnp.floating) and \
                        jnp.finfo(wave_dt).bits > 32:
                    warnings.warn(
                        f"SpMMEngine: wave dtype {np.dtype(wave_dt)} exceeds "
                        f"the fused kernel's f32 accumulation — results "
                        f"carry the request dtype but f32 precision",
                        stacklevel=3)
                k = wave[0].b.shape[0]
                ring = self._staging_ring(np.dtype(wave_dt), k, rec)
                # At most one other wave is alive here: the one in flight
                # (nothing is staged). It was the last wave staged, so it
                # holds this ring's other slot or none of its slots, and
                # the slot taken belongs to a retired wave. take() still
                # waits on that slot's last transfer, as a guard.
                host_b = ring.take(k, cols + pad)
                off = 0
                for r in wave:
                    width = r.b.shape[1]
                    np.copyto(host_b[:, off:off + width], r.b,
                              casting="same_kind")
                    off += width
                host_b[:, cols:] = 0
            with _Span("spmm.stage.put", rec) as put:
                b = jnp.asarray(host_b)
            ring.sent(b)
        self.stats["pad_cols"] += pad
        rec.h2d_bytes = host_b.nbytes
        rec.stage_s, rec.pack_s, rec.put_s = stage.s, pack.s, put.s
        self.trace.stage_s_total += stage.s
        if hidden:
            self.trace.stage_s_hidden += stage.s
        self._staged = _Wave(wave, b, rec)
        return True

    def _dispatch(self) -> None:
        """Launch the staged wave. The kernel call returns immediately
        (async dispatch) — the operand is captured HERE, so a
        ``swap_pattern`` after dispatch never touches an in-flight wave."""
        w = self._staged
        if w is None:
            return
        self._staged = None
        with _Span("spmm.dispatch", w.rec) as span:
            if self._bound is not None:
                w.c = self._bound(w.b, variant=self.variant,
                                  interpret=self.interpret)
            else:
                w.c = self._ops.spmm(self.prep, w.b, variant=self.variant,
                                     interpret=self.interpret)
        w.rec.dispatch_s = span.s
        w.t_dispatch = t0 = span.t0
        w.entries = [self.trace.add(ItemTrace(
            r.rid, w.rec.seq, isinstance(r, _SplitPart),
            None if r.t_submit is None else t0 - r.t_submit))
            for r in w.items]
        self._inflight = w

    def _finish_item(self, r, panel: np.ndarray, t_done: float) -> None:
        if isinstance(r, _SplitPart):
            parent = r.parent
            parent.out[:, r.offset:r.offset + panel.shape[1]] = \
                panel.astype(parent.b.dtype)
            parent._parts_left -= 1
            if parent._parts_left:
                return
            r = parent                     # last part: parent completes
        else:
            r.out = panel.astype(r.b.dtype)
        r.done = True
        r.t_done = t_done
        self.stats["requests"] += 1
        self.finished.append(r)

    def _retire(self) -> None:
        """Block on the in-flight wave's result and hand each request its
        panel back in its own dtype. The measured wall time (dispatch ->
        result on host) feeds the packer's cost model."""
        w = self._inflight
        if w is None:
            return
        self._inflight = None
        rec = w.rec
        with _Span("spmm.retire", rec) as whole:
            with _Span("spmm.retire.wait", rec) as wait:
                jax.block_until_ready(w.c)     # the device finishes
            with _Span("spmm.retire.fetch", rec) as fetch:
                c = np.asarray(w.c)            # device -> host copy
            t_done = time.perf_counter()
            with _Span("spmm.retire.copy", rec) as copy:
                off = 0
                for r, entry in zip(w.items, w.entries):
                    width = r.b.shape[1]
                    self._finish_item(r, c[:, off:off + width], t_done)
                    off += width
                    req = r.parent if isinstance(r, _SplitPart) else r
                    if req.done and req.t_submit is not None:
                        entry.latency_s = t_done - req.t_submit
        rec.wall_s = t_done - w.t_dispatch
        rec.d2h_bytes = c.nbytes
        rec.retire_s, rec.wait_s, rec.fetch_s, rec.copy_s = \
            whole.s, wait.s, fetch.s, copy.s
        self.stats["cols"] += off
        self.stats["waves"] += 1
        self._t_last_done = t_done
        self.scheduler.observe(off, rec.wall_s * 1e6)

    # ``perfbench/harness.py`` reads these two lists by index around its
    # window. They are views of ``trace``, in the order each list used to
    # grow: queue waits at dispatch, wave wall times at retire.
    @property
    def _queue_wait_s(self) -> List[float]:
        return [e.queue_wait_s for e in self.trace.items()
                if e.queue_wait_s is not None]

    @property
    def _wave_wall_s(self) -> List[float]:
        return [e.wall_s for e in self.trace.waves() if e.wall_s is not None]

    # -- serving loop ----------------------------------------------------
    def step(self, retire: bool = True) -> bool:
        """Advance the pipeline one wave: dispatch (staging first if
        nothing is prepped), then — in continuous mode — prep the NEXT
        wave while the device computes, then retire the in-flight wave.
        ``retire=False`` leaves the wave in flight (callers that want to
        act between dispatch and retirement, e.g. a mid-stream
        ``swap_pattern``). Returns False when there was nothing to do."""
        if self._inflight is None:
            if self._staged is None and not self._stage(hidden=False):
                return False
            self._dispatch()
        if self.continuous and self._staged is None and self.queue:
            self._stage(hidden=True)       # overlapped with device compute
        if retire:
            self._retire()
        return True

    def run(self) -> List[SpMMRequest]:
        """Serve until the queue (and pipeline) drains; returns finished
        requests."""
        while self.queue or self._staged is not None \
                or self._inflight is not None:
            self.step()
        return self.finished

    # -- reporting -------------------------------------------------------
    def stats_summary(self) -> Dict[str, Any]:
        """Latency/throughput digest: requests/sec, per-request latency and
        queue-wait p50/p99, per-wave wall p50/p99, and how much host prep
        the overlap pipeline hid. Counts and prep seconds cover everything
        served so far; the percentiles cover the entries ``trace`` holds
        (its last ``TRACE_CAP``). ``serve_bench`` records exactly this."""
        elapsed = 0.0
        if self._t_first_submit is not None \
                and self._t_last_done is not None:
            elapsed = max(0.0, self._t_last_done - self._t_first_submit)
        n = int(self.stats["requests"])
        cost = self.scheduler.cost
        items = self.trace.items()
        prep_s, hidden_s = self.trace.stage_s_total, self.trace.stage_s_hidden
        return {
            "mode": "continuous" if self.continuous else "wave_barrier",
            "requests": n,
            "waves": int(self.stats["waves"]),
            "cols": int(self.stats["cols"]),
            "elapsed_s": elapsed,
            "requests_per_s": (n / elapsed) if elapsed > 0 else 0.0,
            "latency_ms": _percentiles_ms(
                [e.latency_s for e in items if e.latency_s is not None]),
            "queue_wait_ms": _percentiles_ms(self._queue_wait_s),
            "wave_ms": _percentiles_ms(self._wave_wall_s),
            "prep_s_total": prep_s,
            "prep_s_hidden": hidden_s,
            "prep_overlap_fraction": (hidden_s / prep_s) if prep_s > 0
            else 0.0,
            "stage_buf_allocs": int(self.stats["stage_buf_allocs"]),
            "stage_buf_reuses": int(self.stats["stage_buf_reuses"]),
            "cost_model": {
                "us_per_col": cost.us_per_col,
                "launch_overhead_us": cost.launch_overhead_us,
                "n_observed": cost.n_observed,
                "source": cost.source,
                "last_target_cols": self.scheduler.last_target,
            },
        }
