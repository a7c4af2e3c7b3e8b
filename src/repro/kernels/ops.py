"""Public kernel API: format preparation + the ``spmm`` dispatcher.

``ops.spmm(a, b)`` is THE kernel front door: it dispatches on the type of
the (sparse) left operand — ``PreparedOperand`` / ``InCRS`` to the fused
InCRS kernel, ``ShardedPreparedOperand`` (or ``mesh=``) to the row-sharded
path, ``BSR`` to the block-sparse kernel, ``CRS`` to the round-synchronized
index-matching kernel, and a plain dense array to the tiled dense matmul.
The historical per-format entry points (``incrs_spmm``, ``bsr_matmul``,
``index_match_matmul``, ``incrs_spmm_sharded``) remain as one-release
deprecation shims over the same implementations.

On a TPU backend the kernels compile to Mosaic. Without one (the CPU test
backend) they run in Pallas ``interpret`` mode. ``INTERPRET`` is resolved
once from the backend, and ``resolve_interpret`` refuses interpret mode on a
TPU backend, so no kernel behind this module runs in the interpreter on the
chip.
"""
from __future__ import annotations

import dataclasses
import warnings
import weakref
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._deprecation import deprecated
from ..core.bsr import BSR
from ..core.crs import CRS
from ..core.incrs import InCRS
from ..core import mesh_sim as _mesh_sim
from . import ref
from .bsr_spmm import bsr_spmm as _bsr_spmm_kernel
from .flash_attention import flash_attention as _flash_kernel
from .dense_mm import dense_mm as _dense_mm_kernel
from .incrs_gather import incrs_gather as _incrs_gather_kernel
from .incrs_spmm import incrs_spmm as _incrs_spmm_kernel
from .incrs_spmm import incrs_spmm_pipelined as _incrs_spmm_pipelined_kernel
from .incrs_spmm import incrs_spmm_reuse as _incrs_spmm_reuse_kernel
from .index_match_spmm import index_match_spmm as _index_match_kernel
from . import autotune as _autotune
from ..analysis import kernel_check as _kernel_check

INTERPRET = jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> ``INTERPRET``. Interpret mode is the CPU test switch
    only: asking for it on a TPU backend is an error, never a fallback."""
    if interpret is None:
        return INTERPRET
    if interpret and not INTERPRET:
        raise ValueError("interpret mode was requested on a TPU backend; "
                         "kernels here always compile to Mosaic")
    return bool(interpret)


# ----------------------------------------------------------------------
def dense_mm(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
             interpret: bool | None = None):
    """Tiled dense matmul; pads every dim up to its tile size."""
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    _, n = b.shape
    mp, kp, np_ = -(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn
    a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = _dense_mm_kernel(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


# ----------------------------------------------------------------------
def bsr_kernel_meta(bsr: BSR
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """BSR -> kernel block lists ``(row_of + sentinel, col_of, vpos)``.

    Empty block-rows get one explicit zero tile (stably sorted into place)
    so every output block-row is written — the kernel walks block runs, and
    an absent row would leave its output tile holding garbage — and the
    trailing ``row_of`` sentinel is well-defined even for an all-empty
    matrix. ``vpos[q]`` is the slot of real block ``q`` inside the padded
    sequence (pad slots expect zero values).
    """
    deg = np.diff(bsr.row_ptr)
    row_of = np.repeat(np.arange(bsr.n_block_rows, dtype=np.int32),
                       deg.astype(np.int64))
    col_of = bsr.col_idx.astype(np.int32)
    vpos = np.arange(len(col_of), dtype=np.int32)
    empty = np.nonzero(deg == 0)[0].astype(np.int32)
    if empty.size:
        row_all = np.concatenate([row_of, empty])
        col_all = np.concatenate([col_of, np.zeros_like(empty)])
        order = np.argsort(row_all, kind="stable")
        inv = np.empty(order.size, np.int64)
        inv[order] = np.arange(order.size)
        vpos = inv[:len(col_of)].astype(np.int32)
        row_of, col_of = row_all[order], col_all[order]
    row_of = np.concatenate([row_of, row_of[-1:]])       # sentinel
    return row_of.astype(np.int32), col_of, vpos


def prep_bsr(bsr: BSR) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """BSR -> (row_of, col_of, values) flat arrays for the kernel, with
    zero tiles in place for empty block-rows (see ``bsr_kernel_meta``)."""
    row_of, col_of, vpos = bsr_kernel_meta(bsr)
    values = bsr.values
    if len(col_of) != len(values):
        padded = np.zeros((len(col_of),) + bsr.block, values.dtype)
        padded[vpos] = values
        values = padded
    return (jnp.asarray(row_of), jnp.asarray(col_of), jnp.asarray(values))


def _spmm_bsr(bsr: BSR, b, *, bn: int = 128, interpret: bool | None = None):
    """C = BSR(A) @ B through the prefix-counter-steered Pallas kernel."""
    interpret = resolve_interpret(interpret)
    row_of, col_of, values = prep_bsr(bsr)
    k, n = b.shape
    if k != bsr.shape[1]:
        raise ValueError(f"inner dims disagree: A is {bsr.shape}, "
                         f"B is {b.shape}")
    np_ = -(-n // bn) * bn
    b = jnp.pad(b, ((0, 0), (0, np_ - n)))
    out = _bsr_spmm_kernel(row_of, col_of, values, b,
                           n_block_rows=bsr.n_block_rows, bn=bn,
                           interpret=interpret)
    return out[:, :n]


def bsr_matmul_arrays(row_of, col_of, values, b, *, n_block_rows: int,
                      bn: int = 128, interpret: bool | None = None):
    """Same as ``bsr_matmul`` but from pre-prepared (traced) arrays —
    the entry point used by ``sparse.SparseLinear`` inside jit."""
    interpret = resolve_interpret(interpret)
    return _bsr_spmm_kernel(row_of, col_of, values, b,
                            n_block_rows=n_block_rows, bn=bn,
                            interpret=interpret)


# ----------------------------------------------------------------------
def prep_rounds(crs: CRS, rounds: int, rmax: int | None = None,
                pad_rows_to: int = 128, on_overflow: str = "raise",
                dtype=np.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """CRS -> padded per-round (idx, val) of shape (n_rounds, Mp, rmax);
    idx local in [0, R), -1 = pad. Round-major, so each kernel block
    ``(1, rows, rmax)`` is a native (sublane, lane) tile.

    Rows are padded up to a multiple of ``pad_rows_to``; at most R non-zeros
    fit in one round window, so rmax <= R always holds. ``dtype`` sets the
    value array's dtype (the kernels promote to f32 in-wave and return the
    operands' result dtype — see ``index_match_spmm``).

    A caller-supplied ``rmax`` smaller than the densest (row, round) count
    cannot hold every non-zero: ``on_overflow="raise"`` (default) rejects it
    with a ValueError, ``on_overflow="drop"`` keeps the first ``rmax``
    non-zeros per round window and warns about the rest.
    """
    if on_overflow not in ("raise", "drop"):
        raise ValueError(f"on_overflow must be 'raise' or 'drop', "
                         f"got {on_overflow!r}")
    m, n = crs.shape
    n_rounds = max(1, -(-n // rounds))
    counts = np.zeros((m, n_rounds), dtype=np.int64)
    row_of = None
    if crs.nnz:
        row_of = np.repeat(np.arange(m), np.diff(crs.row_ptr).astype(np.int64))
        np.add.at(counts, (row_of, crs.col_idx // rounds), 1)
    rmax_true = int(counts.max(initial=1))
    rmax = rmax_true if rmax is None else rmax
    rmax = max(1, min(rmax, rounds))
    if rmax < rmax_true:
        if on_overflow == "raise":
            raise ValueError(
                f"rmax={rmax} cannot hold the densest (row, round) window "
                f"({rmax_true} non-zeros); raise rmax or pass "
                f"on_overflow='drop'")
        warnings.warn(
            f"prep_rounds: dropping non-zeros beyond slot {rmax} in "
            f"{int((counts > rmax).sum())} overfull (row, round) windows "
            f"(densest holds {rmax_true})", stacklevel=2)
    mp = -(-m // pad_rows_to) * pad_rows_to
    idx = np.full((n_rounds, mp, rmax), -1, dtype=np.int32)
    val = np.zeros((n_rounds, mp, rmax), dtype=dtype)
    if crs.nnz:
        # Non-zeros are sorted by (row, col), hence by (row, round): each
        # (row, round) group is one contiguous run. Slot-within-round =
        # position in the run = global position minus the group's exclusive
        # prefix sum — all rows at once, no Python loop.
        r = crs.col_idx.astype(np.int64) // rounds
        group_start = np.concatenate(
            [[0], np.cumsum(counts.reshape(-1))[:-1]])
        g = row_of * n_rounds + r
        slot = np.arange(crs.nnz, dtype=np.int64) - group_start[g]
        if rmax < rmax_true:
            sel = slot < rmax
            row_of, r, slot = row_of[sel], r[sel], slot[sel]
            idx[r, row_of, slot] = crs.col_idx[sel] % rounds
            val[r, row_of, slot] = crs.values[sel]
        else:
            idx[r, row_of, slot] = crs.col_idx % rounds
            val[r, row_of, slot] = crs.values
    return jnp.asarray(idx), jnp.asarray(val)


def index_match_prepped(ai, av, bi, bv, *, rounds: int = 128,
                        bm: int = 128, bn: int = 128, out_dtype=None,
                        interpret: bool | None = None):
    """Round-synchronized index-matching SpMM from PRE-PREPPED per-round
    (idx, val) operand arrays (``prep_rounds`` output): pads both sides to
    a common rmax and runs the kernel. Returns the PADDED output — callers
    trim to the real (M, N). The plan–execute API uses this to prep the
    fixed sparse operand once and stream right-hand sides."""
    interpret = resolve_interpret(interpret)
    rmax = max(ai.shape[2], bi.shape[2])
    ai = jnp.pad(ai, ((0, 0), (0, 0), (0, rmax - ai.shape[2])),
                 constant_values=-1)
    av = jnp.pad(av, ((0, 0), (0, 0), (0, rmax - av.shape[2])))
    bi = jnp.pad(bi, ((0, 0), (0, 0), (0, rmax - bi.shape[2])),
                 constant_values=-1)
    bv = jnp.pad(bv, ((0, 0), (0, 0), (0, rmax - bv.shape[2])))
    out_dtype = (jnp.result_type(av.dtype, bv.dtype) if out_dtype is None
                 else jnp.dtype(out_dtype))
    return _index_match_kernel(ai, av, bi, bv, rounds=rounds, bm=bm, bn=bn,
                               out_dtype=out_dtype, interpret=interpret)


def _resolve_matched_tiles(m: int, n: int, k: int, rounds, bm, bn,
                           interpret: bool):
    """Fill ``None`` (rounds, bm, bn) from the autotuner's matched-family
    cache for this (m, n, k, backend); hardware defaults otherwise."""
    if rounds is None or bm is None or bn is None:
        tuned = _autotune.lookup(_autotune.matched_cache_key(
            m, n, k, _autotune.backend_name(interpret)))
        if tuned is not None:
            rounds = (tuned.rounds or 128) if rounds is None else rounds
            bm = tuned.bm if bm is None else bm
            bn = tuned.bn if bn is None else bn
    return (128 if rounds is None else rounds,
            128 if bm is None else bm,
            128 if bn is None else bn)


def _spmm_index_match(a: CRS, bt: CRS, *, rounds: int | None = None,
                      bm: int | None = None, bn: int | None = None,
                      interpret: bool | None = None):
    """C = A @ Bt.T via the round-synchronized index-matching kernel
    (paper Alg. 2 on the MXU). Returns C[:M, :N] unpadded. ``None``
    tile/round params resolve from the autotuner's matched-family cache
    (``autotune.tune_index_match``) before falling back to 128."""
    interpret = resolve_interpret(interpret)
    if a.shape[1] != bt.shape[1]:
        raise ValueError(f"inner dims disagree: A is {a.shape}, "
                         f"Bt is {bt.shape} (expected equal col counts)")
    rounds, bm, bn = _resolve_matched_tiles(
        a.shape[0], bt.shape[0], a.shape[1], rounds, bm, bn, interpret)
    ai, av = prep_rounds(a, rounds, pad_rows_to=bm)
    bi, bv = prep_rounds(bt, rounds, pad_rows_to=bn)
    out = index_match_prepped(ai, av, bi, bv, rounds=rounds, bm=bm, bn=bn,
                              interpret=interpret)
    return out[:a.shape[0], :bt.shape[0]]


# id()-keyed weakref memo, same contract as _PREP_CACHE: the CRS is
# immutable once converted; entries die with their operand.
_INCRS_CACHE: Dict[int, Tuple[weakref.ref, InCRS]] = {}


def _incrs_of(crs: CRS) -> InCRS:
    """InCRS view of a CRS operand, memoized per live object (the densify
    engine of the SpGEMM dispatch converts both operands; repeated calls
    must not re-pack counters every time)."""
    hit = _INCRS_CACHE.get(id(crs))
    if hit is not None and hit[0]() is crs:
        return hit[1]
    incrs = InCRS.from_crs(crs)
    key = id(crs)
    _INCRS_CACHE[key] = (weakref.ref(crs), incrs)
    weakref.finalize(crs, _INCRS_CACHE.pop, key, None)
    return incrs


_SPGEMM_VARIANTS = ("auto", "condense_merge", "densify", "reference")


def _spmm_spgemm(a: CRS, b, *, rounds: int | None = None,
                 bm: int | None = None, bn: int | None = None,
                 variant: str = "auto", interpret: bool | None = None):
    """C = A @ Bt.T for sparse A and sparse Bt — the SpGEMM dispatch.

    Engines:
      * ``"condense_merge"`` — the two-pass round-stripe pipeline
        (``spgemm.condense_merge_prepped``), bitwise identical to the
        reference on identically prepped operands;
      * ``"densify"``        — gather Bt dense on-device, then the fused
        InCRS SpMM (the pre-existing two-pass baseline);
      * ``"reference"``      — the fused one-pass ``index_match_spmm``
        engine, also the bitwise oracle for condense_merge;
      * ``"auto"``           — ``mesh_sim.spgemm_cost`` +
        ``autotune.pick_spgemm_engine`` pick among the three for this
        operand pair and backend.
    """
    if variant not in _SPGEMM_VARIANTS:
        raise ValueError(f"variant must be one of {_SPGEMM_VARIANTS}, "
                         f"got {variant!r}")
    interpret = resolve_interpret(interpret)
    bt = b.crs if isinstance(b, InCRS) else b
    if a.shape[1] != bt.shape[1]:
        raise ValueError(f"inner dims disagree: A is {a.shape}, "
                         f"Bt is {bt.shape} (expected equal col counts)")
    m, n = a.shape[0], bt.shape[0]
    rounds, bm, bn = _resolve_matched_tiles(m, n, a.shape[1], rounds, bm, bn,
                                            interpret)
    if variant == "auto":
        cost = _mesh_sim.spgemm_cost_for(a, bt, rounds=rounds, bm=bm, bn=bn)
        variant = _autotune.pick_spgemm_engine(cost, interpret)
    if variant == "reference":
        return _spmm_index_match(a, bt, rounds=rounds, bm=bm, bn=bn,
                                 interpret=interpret)
    if variant == "densify":
        dense_b = incrs_to_dense(_incrs_of(bt), interpret=interpret).T
        return _spmm_incrs(_incrs_of(a), dense_b, interpret=interpret)
    from .. import spgemm as _spgemm            # circular at module scope
    ai, av = prep_rounds(a, rounds, pad_rows_to=bm)
    bi, bv = prep_rounds(bt, rounds, pad_rows_to=bn)
    out = _spgemm.condense_merge_prepped(ai, av, bi, bv, rounds=rounds,
                                         bm=bm, bn=bn, interpret=interpret)
    return out[:m, :n]


# ----------------------------------------------------------------------
def prep_sections(incrs: InCRS, pad_rows_to: int = 8
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """InCRS -> padded per-(section, row) (idx, val) using ONLY the packed
    counter-vectors for location (the paper's access path): the prefix word
    gives each section's start offset inside the row, the block counts give
    its length. No row scan ever happens.

    Both arrays are section-major, ``(n_sections, Mp, smax)``: a kernel
    block ``(1, bm, smax)`` is then one section stripe of ``bm`` rows whose
    last two dims map onto native (sublane, lane) tiles.

    Fully vectorized: one batched ``_unpack64`` over the whole counter array
    yields every (start, count) span at once; the gather + scatter runs over
    all non-zeros in one shot.
    """
    m, n = incrs.shape
    crs = incrs.crs
    n_sections = incrs.n_sections
    prefix, blocks = incrs.counters_unpacked()
    cnt = blocks.sum(axis=-1)                          # (m, n_sections)
    starts = crs.row_ptr[:m, None] + prefix            # (m, n_sections)
    smax = max(1, int(cnt.max(initial=0)))
    mp = -(-m // pad_rows_to) * pad_rows_to
    idx = np.full((n_sections, mp, smax), -1, dtype=np.int32)
    val = np.zeros((n_sections, mp, smax), dtype=np.float32)
    total = int(cnt.sum())
    if total:
        flat_cnt = cnt.reshape(-1)
        # slot-within-section for every NZ: global position minus its
        # group's exclusive prefix sum (groups are (row, section) spans).
        off = np.concatenate([[0], np.cumsum(flat_cnt)[:-1]])
        slot = np.arange(total, dtype=np.int64) - np.repeat(off, flat_cnt)
        src = np.repeat(starts.reshape(-1), flat_cnt) + slot
        grid_i, grid_s = np.indices((m, n_sections))
        rows = np.repeat(grid_i.reshape(-1), flat_cnt)
        secs = np.repeat(grid_s.reshape(-1), flat_cnt)
        idx[secs, rows, slot] = crs.col_idx[src] - secs * incrs.section
        val[secs, rows, slot] = crs.values[src]
    return jnp.asarray(idx), jnp.asarray(val)


# ----------------------------------------------------------------------
# eq=False: the generated __eq__/__hash__ would compare jnp arrays and raise;
# identity semantics are the correct ones for a cached device artifact.
@dataclasses.dataclass(frozen=True, eq=False)
class PreparedOperand:
    """Device-ready section-stripe form of one InCRS operand.

    Prep (counter unpack + scatter) runs once; every subsequent SpMM against
    the same operand reuses the arrays. Produced by ``prepare_incrs`` which
    memoizes per live InCRS object.
    """
    idx: jnp.ndarray              # (n_sections, Mp, smax) int32, -1 = pad
    val: jnp.ndarray              # (n_sections, Mp, smax) f32
    shape: Tuple[int, int]        # original (M, K) of the sparse operand
    section: int

    @property
    def n_sections(self) -> int:
        return self.idx.shape[0]

    @property
    def padded_rows(self) -> int:
        return self.idx.shape[1]

    @property
    def smax(self) -> int:
        return self.idx.shape[2]


# id() can be recycled after an object dies — each cache entry carries a
# weakref that must still point at the SAME object to count as a hit.
_PREP_CACHE: Dict[Tuple[int, int, int, int],
                  Tuple[weakref.ref, PreparedOperand]] = {}
_PREP_CACHE_MAX = 64


def prepare_incrs(incrs: InCRS, *, pad_rows_to: int = 128,
                  pattern=None) -> PreparedOperand:
    """Prep an InCRS operand for the fused SpMM kernel, memoized.

    Repeated SpMMs against the same live InCRS object (serving engines,
    sparse layers) pay the host-side format prep exactly once.

    The operand is treated as IMMUTABLE once prepped: mutating
    ``incrs.crs`` in place afterwards leaves the cached arrays stale.
    Rebuild the InCRS (or call ``invalidate_prepared``) after mutation.

    ``pattern`` (a ``sparse.SparsityPattern``) keys the memo on the
    pattern lineage instead, guarded by BOTH the pattern version and this
    InCRS object's identity: a repack (version bump) invalidates and
    rebuilds, and so does rebuilding the InCRS from updated values under
    the same pattern — see ``prepare_versioned``.
    """
    if pattern is not None:
        return prepare_versioned(
            pattern,
            f"incrs/{incrs.section}/{incrs.block}/{pad_rows_to}",
            lambda: PreparedOperand(
                *prep_sections(incrs, pad_rows_to=pad_rows_to),
                incrs.shape, incrs.section),
            token=incrs)
    key = (id(incrs), incrs.section, incrs.block, pad_rows_to)
    hit = _PREP_CACHE.get(key)
    if hit is not None and hit[0]() is incrs:
        # Promote to most-recently-used: dict order is insertion order, so
        # re-inserting makes eviction (pop of the first key) true LRU — a
        # hot operand prepped early must outlive cold late-comers.
        _PREP_CACHE[key] = _PREP_CACHE.pop(key)
        return hit[1]
    idx, val = prep_sections(incrs, pad_rows_to=pad_rows_to)
    prep = PreparedOperand(idx, val, incrs.shape, incrs.section)
    if len(_PREP_CACHE) >= _PREP_CACHE_MAX:
        _PREP_CACHE.pop(next(iter(_PREP_CACHE)))      # least recently used
    _PREP_CACHE[key] = (weakref.ref(incrs), prep)
    # Drop the entry (and its device arrays) the moment the operand dies —
    # without this, a dead entry pins idx/val until the cap-eviction path.
    weakref.finalize(incrs, _PREP_CACHE.pop, key, None)
    return prep


def invalidate_prepared(incrs: InCRS) -> None:
    """Evict every cached ``PreparedOperand`` of ``incrs`` — required after
    mutating its CRS data in place (prep treats operands as immutable)."""
    for k in [k for k in _PREP_CACHE if k[0] == id(incrs)]:
        _PREP_CACHE.pop(k, None)


# ----------------------------------------------------------------------
# Pattern-version-keyed prep: entries are owned by a sparsity-pattern
# LINEAGE (``sparse.pattern.SparsityPattern`` — any object with ``uid`` and
# ``version`` works; ops stays import-free of the sparse layer). A repack
# bumps the pattern's version, so the next lookup rebuilds the
# ``PreparedOperand``/``ShardedPreparedOperand`` and replaces the stale
# entry — the cache can never serve a pre-repack operand for an evolved
# pattern. An optional ``token`` (the source InCRS) additionally guards
# object identity: values can change WITHOUT a version bump (training on a
# fixed pattern), so an operand rebuilt from updated weights must miss.
_VERSIONED_CACHE: Dict[Tuple[int, str],
                       Tuple[int, object, object]] = {}
_VERSIONED_CACHE_MAX = 32


def prepare_versioned(pattern, flavor: str, build, token=None):
    """Memoize ``build()`` under ``(pattern.uid, flavor)``, guarded by
    ``pattern.version`` AND (when given) the identity of the live source
    object ``token``: a version mismatch (the pattern was repacked) or a
    different/dead token (the source was rebuilt — possibly with updated
    values) invalidates the entry and rebuilds. LRU-evicted at the cap,
    same policy as the per-object prep cache above."""
    key = (pattern.uid, str(flavor))
    hit = _VERSIONED_CACHE.get(key)
    if hit is not None and hit[0] == pattern.version and \
            (hit[1] is None or hit[1]() is token):
        _VERSIONED_CACHE[key] = _VERSIONED_CACHE.pop(key)   # LRU promote
        return hit[2]
    prep = build()
    _VERSIONED_CACHE.pop(key, None)
    if len(_VERSIONED_CACHE) >= _VERSIONED_CACHE_MAX:
        _VERSIONED_CACHE.pop(next(iter(_VERSIONED_CACHE)))
    _VERSIONED_CACHE[key] = (
        pattern.version, weakref.ref(token) if token is not None else None,
        prep)
    return prep


def invalidate_pattern(pattern) -> None:
    """Drop every versioned prep entry of ``pattern``'s lineage (explicit
    eviction — version bumps already invalidate lazily)."""
    for k in [k for k in _VERSIONED_CACHE if k[0] == pattern.uid]:
        _VERSIONED_CACHE.pop(k, None)


# ----------------------------------------------------------------------
# Row-sharded prep: the paper's mesh scales by giving each comparator-mesh
# row its OWN slice of the sparse operand while the dense operand is shared
# across the mesh (§IV); Sextans/SpArch partition the sparse matrix across
# compute units the same way. Here each mesh device owns one contiguous
# output-row stripe panel of the section stripes; the dense RHS stays
# replicated and per-shard output panels concatenate along rows.
def shard_axes(mesh: Mesh, axis) -> Tuple[Tuple[str, ...], int]:
    """Normalize the shard-axis spec and count the shards it yields:
    ``axis=None`` -> every mesh axis (one shard per device), a name or
    tuple of names otherwise. Returns ``(axes, n_shards)``. The single
    source of the axes->shard-count rule — the sharded packer in
    ``sparse.linear`` uses it too, so the two always agree."""
    if axis is None:
        axes = tuple(mesh.axis_names)
    else:
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = 1
    for a in axes:
        n_shards *= sizes[a]
    return axes, n_shards


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedPreparedOperand:
    """Row-sharded section-stripe form of one InCRS operand, bound to a
    mesh placement: shard ``s`` holds global output rows
    ``[s * rows_per_shard, (s + 1) * rows_per_shard)`` (the tail shard may
    be partially empty) and ``idx``/``val`` carry a ``NamedSharding`` over
    ``axes`` so no device ever materializes another shard's stripes."""
    idx: jnp.ndarray              # (n_shards, n_sections, Rp, smax) int32
    val: jnp.ndarray              # (n_shards, n_sections, Rp, smax) f32
    shape: Tuple[int, int]        # global (M, K) of the sparse operand
    section: int
    rows_per_shard: int           # real output rows owned by each shard
    mesh: Mesh
    axes: Tuple[str, ...]         # mesh axes the shard dim is split over

    @property
    def n_shards(self) -> int:
        return self.idx.shape[0]

    @property
    def n_sections(self) -> int:
        return self.idx.shape[1]

    @property
    def padded_rows(self) -> int:
        return self.idx.shape[2]


def prepare_incrs_sharded(incrs: InCRS, mesh: Mesh, *, axis=None,
                          pad_rows_to: int = 128,
                          pattern=None) -> ShardedPreparedOperand:
    """Partition an InCRS operand into per-device output-row stripe shards.

    The section stripes are built once on the host (the same vectorized
    ``prep_sections`` path as the single-device prep — per-row content is
    bit-identical), split into ``n_shards`` contiguous row ranges, and
    placed with a ``NamedSharding`` so each device of ``mesh`` holds only
    its own panel. ``axis`` (default: every mesh axis) names the mesh
    axes the shard dimension is split over. ``pattern`` memoizes the shard
    prep on the pattern lineage, invalidated by repack version bumps —
    see ``prepare_versioned``.
    """
    if pattern is not None:
        axes_n, _ = shard_axes(mesh, axis)
        return prepare_versioned(
            pattern,
            f"incrs_sharded/{id(mesh)}/{axes_n}/{incrs.section}/"
            f"{incrs.block}/{pad_rows_to}",
            lambda: prepare_incrs_sharded(incrs, mesh, axis=axis,
                                          pad_rows_to=pad_rows_to),
            token=incrs)
    axes, n_shards = shard_axes(mesh, axis)
    m, _ = incrs.shape
    gi, gv = prep_sections(incrs, pad_rows_to=1)
    gi, gv = np.asarray(gi), np.asarray(gv)            # (Si, m, smax)
    rows_per_shard = -(-m // n_shards)
    rp = -(-rows_per_shard // pad_rows_to) * pad_rows_to
    si, _, smax = gi.shape
    idx = np.full((n_shards, si, rp, smax), -1, dtype=np.int32)
    val = np.zeros((n_shards, si, rp, smax), dtype=np.float32)
    for s in range(n_shards):
        lo = s * rows_per_shard
        hi = min(m, lo + rows_per_shard)
        if hi > lo:
            idx[s, :, :hi - lo] = gi[:, lo:hi]
            val[s, :, :hi - lo] = gv[:, lo:hi]
    sharding = NamedSharding(mesh, P(axes))
    return ShardedPreparedOperand(
        jax.device_put(jnp.asarray(idx), sharding),
        jax.device_put(jnp.asarray(val), sharding),
        incrs.shape, incrs.section, rows_per_shard, mesh, axes)


def _spmm_incrs_sharded(a: InCRS | ShardedPreparedOperand, b, *,
                        mesh: Mesh | None = None, axis=None,
                        pad_rows_to: int = 128, bm: int = 128,
                        bn: int | None = None, variant: str = "auto",
                        interpret: bool | None = None):
    """C = A @ B with A row-sharded across the mesh.

    Each device runs the fused kernel over its own stripe panel under
    ``shard_map``; B is broadcast (replicated in-spec) to every device and
    the per-shard output panels concatenate along output rows — A is never
    gathered dense OR sparse onto a single device. At the default
    ``pad_rows_to`` the per-shard row tiles match the single-device
    ``incrs_spmm`` tiles exactly (same stripe content, same dot shapes),
    so results match it bitwise; a smaller ``pad_rows_to`` shrinks the
    local row tile and is exact only to dot-reduction reassociation.
    """
    if isinstance(a, ShardedPreparedOperand):
        prep = a
    else:
        if mesh is None:
            raise ValueError("row-sharded spmm needs mesh= when given a "
                             "raw InCRS (or pass a ShardedPreparedOperand)")
        prep = prepare_incrs_sharded(a, mesh, axis=axis,
                                     pad_rows_to=pad_rows_to)
    m, k = prep.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: A is {prep.shape}, "
                         f"B is {b.shape}")
    rps, section = prep.rows_per_shard, prep.section

    def local(idx, val, bl):
        # bm clamps to the shard-local panel inside _spmm_incrs (the
        # per-shard tile can be narrower than the global default).
        p1 = PreparedOperand(idx[0], val[0], (rps, k), section)
        return _spmm_incrs(p1, bl, bm=bm, bn=bn, variant=variant,
                           interpret=interpret)

    spec0 = P(prep.axes)
    y = shard_map(local, mesh=prep.mesh, in_specs=(spec0, spec0, P()),
                  out_specs=P(prep.axes), check_vma=False)(
        prep.idx, prep.val, jnp.asarray(b))
    return y[:m]


# ----------------------------------------------------------------------
# Row-panel accumulator budget of the stripe-reuse/pipelined variants
# (bm x Np f32 held in VMEM for a whole row tile) — beyond this, fall
# back to the re-expanding order whose accumulator is one (bm, bn) tile.
# Single source of truth is the static footprint model in
# ``analysis.vmem`` (the autotuner's feasibility filter and this
# dispatch gate both read it, so the two always agree).
_REUSE_PANEL_BYTES = _autotune.PANEL_BYTES

_INCRS_KERNELS = {"expand": _incrs_spmm_kernel,
                  "reuse": _incrs_spmm_reuse_kernel,
                  "pipelined": _incrs_spmm_pipelined_kernel}


def _spmm_incrs(a: InCRS | PreparedOperand, b, *, bm: int = 128,
                bn: int | None = None, variant: str = "auto",
                interpret: bool | None = None):
    """C = A @ B fused: InCRS section stripes are expanded in VMEM
    and contracted on the MXU in the same grid step — the dense (M, K)
    intermediate of ``incrs_to_dense -> dense_mm`` never touches HBM.

    ``a`` may be a raw InCRS (prepped through the memo cache) or an explicit
    ``PreparedOperand``. ``bn`` defaults to a wide (512-capped) col tile:
    in the expand order every col tile re-expands the section stripe, so
    fewer/wider tiles do strictly less decompression work (the reuse order
    expands once per row tile regardless). Returns C[:M, :N] unpadded, f32.

    ``variant`` picks the grid order (see ``kernels/incrs_spmm.py``):
    "expand" re-expands the stripe per col tile, "reuse" expands once per
    (row tile, section) and reuses it across col tiles behind an
    output-stationary row-panel accumulator, "pipelined" additionally
    double-buffers the RHS stream from HBM. "auto" (default) first
    consults the autotuner's tuning cache for this problem shape (a
    ``sparse.api.plan``-tuned config or a prior ``kernels.autotune.tune``
    run); with no tuned entry it picks by the autotuner's cycle-level
    cost model (one-time log says which variant won and why).
    """
    if variant not in ("auto", "expand", "reuse", "pipelined"):
        raise ValueError(f"variant must be 'auto', 'expand', 'reuse' or "
                         f"'pipelined', got {variant!r}")
    explicit_variant = variant != "auto"
    interpret = resolve_interpret(interpret)
    prep = a if isinstance(a, PreparedOperand) else \
        prepare_incrs(a, pad_rows_to=bm)
    m, k = prep.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: A is {prep.shape}, "
                         f"B is {b.shape}")
    if variant == "auto":
        tuned = _autotune.lookup(_autotune.cache_key(
            prep.padded_rows, prep.n_sections, prep.smax,
            prep.section, n, _autotune.backend_name(interpret)))
        if tuned is not None and bn is None:
            variant, bm, bn = tuned.variant, tuned.bm, tuned.bn
    if bn is None:
        # Fewest ~512-wide tiles, then shrink bn to the 128-multiple that
        # just covers them — bounds padding waste at <128 cols/tile instead
        # of up to 511 while keeping stripe re-expansion minimal.
        np128 = -(-n // 128) * 128
        tiles = -(-np128 // 512)
        bn = -(-np128 // (tiles * 128)) * 128
    kp = prep.n_sections * prep.section
    np_ = -(-n // bn) * bn
    if variant == "auto":
        variant = _autotune.model_pick_variant(
            prep.padded_rows, np_, n_sections=prep.n_sections,
            smax=prep.smax, section=prep.section, bm=bm, bn=bn,
            interpret=interpret)
    elif explicit_variant:
        # An explicitly requested variant may ignore the panel working-
        # set *heuristic*, but never the physical per-core VMEM budget:
        # prove the launch fits before it runs (KernelConfigError names
        # the violated term) instead of OOMing on hardware.
        _kernel_check.require_feasible(
            variant, m=prep.padded_rows, n=np_, bm=bm, bn=bn,
            n_sections=prep.n_sections, smax=prep.smax,
            section=prep.section,
            rules=(_kernel_check.RULE_VMEM,),
            context=f"spmm variant={variant!r}")
    b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    kernel = _INCRS_KERNELS[variant]
    out = kernel(prep.idx, prep.val, b, section=prep.section,
                 bm=bm, bn=bn, interpret=interpret)
    return out[:m, :n]


def incrs_to_dense(incrs: InCRS, *, bm: int = 8,
                   interpret: bool | None = None):
    """Densify an InCRS matrix on-device via the gather kernel (the TWO-pass
    baseline path; kept for tests/benchmarks and ad-hoc densification).
    Prep is memoized per live object — see ``prepare_incrs`` for the
    immutability contract."""
    interpret = resolve_interpret(interpret)
    prep = prepare_incrs(incrs, pad_rows_to=bm)
    out = _incrs_gather_kernel(prep.idx, prep.val, section=incrs.section,
                               bm=bm, interpret=interpret)
    return out[:incrs.shape[0], :incrs.shape[1]]


# ----------------------------------------------------------------------
def spmm(a, b, *, mesh: Mesh | None = None, axis=None,
         rounds: int | None = None,
         bm: int = 128, bn: int | None = None, variant: str = "auto",
         pad_rows_to: int = 128, interpret: bool | None = None):
    """C = A @ B — THE kernel front door, dispatched on the format of A.

    One call covers every kernel family (the paper's claim — one
    representation and one locate–compute architecture for every access
    order — stated as API):

      * ``PreparedOperand`` / ``InCRS``      -> fused InCRS SpMM
        (``variant`` picks the grid order, "auto" by shape);
      * ``ShardedPreparedOperand`` (or a raw ``InCRS`` with ``mesh=``)
        -> row-sharded fused SpMM under ``shard_map``;
      * ``BSR``                              -> block-sparse kernel
        steered by prefix counters;
      * ``CRS`` x ``CRS``/``InCRS`` (B = the sparse B^T, row-stored)
        -> SpGEMM: ``variant`` picks "condense_merge" (round-stripe
        two-pass), "densify" (gather-then-fused-SpMM), "reference" (the
        fused index-matching kernel, paper Alg. 2) or "auto" (the
        ``mesh_sim.spgemm_cost`` oracle decides); window = ``rounds``;
      * a plain dense 2-D array              -> tiled dense matmul.

    Returns C[:M, :N] unpadded, f32 accumulation everywhere. The
    spec-level face of the same dispatch is ``sparse.api.plan`` /
    ``sparse.Linear``, which add pattern resolution, packing, and the
    sparsity lifecycle on top.
    """
    if isinstance(a, ShardedPreparedOperand):
        return _spmm_incrs_sharded(a, b, bm=bm, bn=bn, variant=variant,
                                   interpret=interpret)
    if isinstance(a, (PreparedOperand, InCRS)):
        if mesh is not None:
            if not isinstance(a, InCRS):
                raise ValueError(
                    "cannot re-shard an already-built single-device "
                    "PreparedOperand — pass the raw InCRS with mesh=, or "
                    "a ShardedPreparedOperand")
            return _spmm_incrs_sharded(a, b, mesh=mesh, axis=axis,
                                       pad_rows_to=pad_rows_to, bm=bm,
                                       bn=bn, variant=variant,
                                       interpret=interpret)
        return _spmm_incrs(a, b, bm=bm, bn=bn, variant=variant,
                           interpret=interpret)
    if isinstance(a, BSR):
        return _spmm_bsr(a, b, bn=128 if bn is None else bn,
                         interpret=interpret)
    if isinstance(a, CRS):
        if not isinstance(b, (CRS, InCRS)):
            raise TypeError(
                "spmm with a CRS left operand runs sparse x sparse "
                "C = A @ B^T and needs B^T sparse too (CRS or InCRS); "
                "densify one side or use the InCRS path for "
                "sparse-times-dense")
        return _spmm_spgemm(a, b, rounds=rounds,
                            bm=None if bm == 128 else bm, bn=bn,
                            variant=variant, interpret=interpret)
    if hasattr(a, "ndim") and np.ndim(a) == 2:
        return dense_mm(jnp.asarray(a), b, interpret=interpret)
    raise TypeError(f"spmm does not know the operand format "
                    f"{type(a).__name__}; expected PreparedOperand, "
                    f"ShardedPreparedOperand, InCRS, BSR, CRS or a dense "
                    f"2-D array")


# One-release deprecation shims over the per-format entry points — same
# implementations as the dispatcher, so outputs are bit-identical (pinned
# by tests/test_api.py).
incrs_spmm = deprecated("ops.incrs_spmm", _spmm_incrs, "ops.spmm(a, b)")
incrs_spmm_sharded = deprecated("ops.incrs_spmm_sharded",
                                _spmm_incrs_sharded,
                                "ops.spmm(a, b, mesh=...)")
bsr_matmul = deprecated("ops.bsr_matmul", _spmm_bsr, "ops.spmm(bsr, b)")
index_match_matmul = deprecated("ops.index_match_matmul", _spmm_index_match,
                                "ops.spmm(a_crs, bt_crs, rounds=...)")


# ----------------------------------------------------------------------
def flash_mha(q, k, v, *, window=None, soft_cap=None, bq: int = 128,
              bk: int = 128, interpret: bool | None = None):
    """Grouped-query flash attention through the Pallas kernel.

    q: (B, Sq, KV, G, hd); k/v: (B, Sk, KV, hd). Causal over absolute
    positions 0..S-1 (prefill/train layout). Returns (B, Sq, KV, G, hd).
    """
    interpret = resolve_interpret(interpret)
    b, sq, kv, g, hd = q.shape
    _, sk, _, _ = k.shape
    sqp = -(-sq // bq) * bq
    skp = -(-sk // bk) * bk
    qf = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
    # (L=B*KV*G, S, hd) lanes; k lanes (B*KV, S, hd)
    ql = qf.transpose(0, 2, 3, 1, 4).reshape(b * kv * g, sqp, hd)
    kl = kf.transpose(0, 2, 1, 3).reshape(b * kv, skp, hd)
    vl = vf.transpose(0, 2, 1, 3).reshape(b * kv, skp, hd)
    out = _flash_kernel(ql, kl, vl, g=g, window=window, soft_cap=soft_cap,
                        bq=bq, bk=bk, interpret=interpret)
    out = out.reshape(b, kv, g, sqp, hd).transpose(0, 3, 1, 2, 4)
    return out[:, :sq]


__all__ = [
    "INTERPRET", "resolve_interpret", "spmm", "dense_mm", "bsr_kernel_meta",
    "prep_bsr", "bsr_matmul_arrays",
    "prep_rounds", "index_match_prepped", "prep_sections", "PreparedOperand",
    "prepare_incrs", "invalidate_prepared", "incrs_to_dense",
    "prepare_versioned", "invalidate_pattern",
    "ShardedPreparedOperand", "prepare_incrs_sharded",
    "shard_axes",
    # one-release deprecation shims (use ops.spmm)
    "incrs_spmm", "incrs_spmm_sharded", "bsr_matmul", "index_match_matmul",
    "flash_mha", "ref",
]
