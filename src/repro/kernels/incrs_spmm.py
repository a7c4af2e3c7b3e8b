"""Fused InCRS SpMM: section-stripe decompression + MXU accumulate, one pass.

The two-pass pipeline (``incrs_gather`` -> dense ``(M, K)`` in HBM ->
``dense_mm``) pays the full dense-matmul memory traffic the InCRS format was
designed to avoid. This kernel fuses the two: per ``(row-tile, col-tile,
section)`` grid step it

  1. expands the section's sparse stripe (padded per-(section, row)
     ``idx``/``val`` from ``ops.prep_sections``, located purely via the
     packed counter-vectors) into a dense ``(bm, section)`` slab in VMEM, and
  2. immediately contracts that slab against the matching ``(section, bn)``
     tile of the dense operand into a VMEM f32 accumulator.

The decompressed stripe lives only in VMEM for the duration of one grid
step — the ``(M, K)`` dense intermediate never exists in HBM. The section
grid axis is the reduction ("operand stream" of the paper's Fig. 2 mesh);
row/col tiles are parallel. This is the same fusion that streaming SpMM
accelerators (Sextans, SpArch) perform between their decompression front-end
and their accumulation array.

Three grid orders are provided (``ops.spmm`` picks by tuned config or the
autotuner's cost model):

* ``incrs_spmm``           — grid (row-tile, col-tile, section), accumulator
  per output tile; every col tile re-expands the section stripe.
* ``incrs_spmm_reuse``     — grid (row-tile, section, col-tile); the stripe
  is expanded ONCE into a VMEM scratch and reused across all col tiles, with
  an output-stationary (bm, N) row-panel accumulator.
* ``incrs_spmm_pipelined`` — grid (row-tile,); the dense RHS stays in HBM
  and is streamed block-by-block through a double-buffered VMEM window
  (manual DMA), so the next (section, bn) block is in flight while the MXU
  contracts the current one. The (bm, N) out block is output-stationary in
  VMEM for the whole row panel — partial sums never round-trip HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# TPU f32 sublane granularity: row tiles are kept to multiples of this so
# padded panels still map onto native (8, 128) vregs.
_SUBLANE = 8


def _resolve_row_tile(m: int, bm: int) -> tuple[int, int]:
    """Resolve the row tile for an ``m``-row operand.

    A row-sharded operand hands each device a panel that may be smaller
    than one default row tile, or padded to a granularity the tile does
    not divide. The old answer — ``math.gcd(bm, m)`` — silently collapses
    to ``bm=1`` on odd panels (127 rows -> 127 one-row grid steps). New
    rule: shrink ``bm`` to the sublane-rounded panel height, then pad the
    panel up to a whole number of tiles. Returns ``(bm, padded_m)``.
    """
    bm = max(1, min(bm, -(-m // _SUBLANE) * _SUBLANE))
    return bm, -(-m // bm) * bm


def _pad_rows(idx: jnp.ndarray, val: jnp.ndarray,
              padded_m: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pad the row axis with empty stripes (idx=-1 rows expand to zeros)."""
    m = idx.shape[1]
    if padded_m == m:
        return idx, val
    pad = ((0, 0), (0, padded_m - m), (0, 0))
    return (jnp.pad(idx, pad, constant_values=-1),
            jnp.pad(val, pad))


def _check_grid(m: int, n: int, bm: int, bn: int,
                k: int, n_sections: int, section: int) -> None:
    # ValueError, not assert: these guard user-supplied shapes and must
    # survive `python -O` (same bug class PR 3 fixed in SpMMEngine.submit).
    if m % bm != 0 or n % bn != 0:
        raise ValueError(
            f"operand ({m}, {n}) not tileable by (bm={bm}, bn={bn})")
    if k != n_sections * section:
        raise ValueError(
            f"dense operand has {k} rows, InCRS stripes describe "
            f"{n_sections} x {section} = {n_sections * section}")


def _expand_stripe(idx, val, section: int) -> jnp.ndarray:
    """Expand one (bm, smax) section stripe to dense (bm, section).

    Slot k of every row is pulled out as a (bm, 1) column by a masked lane
    reduction and scattered with a lane compare against the column iota.
    Columns within a (row, section) are distinct, so each output element
    is written by at most one slot and the result is exact. Everything is
    2-D (no gather, no 3-D one-hot), which is what Mosaic lowers.
    """
    bm, smax = idx.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, section), 1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (bm, smax), 1)
    val = val.astype(jnp.float32)

    def slot(k, stripe):
        at_k = slots == k
        col_k = jnp.sum(jnp.where(at_k, idx, 0), axis=1, keepdims=True)
        val_k = jnp.sum(jnp.where(at_k, val, 0.0), axis=1, keepdims=True)
        return jnp.where(col_k == cols, val_k, stripe)

    return jax.lax.fori_loop(0, smax, slot,
                             jnp.zeros((bm, section), jnp.float32))


def _kernel(idx_ref, val_ref, b_ref, o_ref, acc_ref, *, section: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Dense stripe of A for this (row-tile, section) — exists only in VMEM.
    stripe = _expand_stripe(idx_ref[0], val_ref[0], section)
    acc_ref[...] += jnp.dot(stripe, b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("section", "bm", "bn", "interpret"))
def incrs_spmm(idx: jnp.ndarray, val: jnp.ndarray, b: jnp.ndarray, *,
               section: int = 256, bm: int = 128, bn: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """C[M, N] = decompress(idx, val) @ B without materializing the left
    operand in HBM.

    idx : (n_sections, M, smax) int32 local column within section, -1 = pad
    val : (n_sections, M, smax) values
    b   : (n_sections * section, N) dense operand (pre-padded)
    """
    n_sections, m, smax = idx.shape
    k, n = b.shape
    bm, mp = _resolve_row_tile(m, bm)
    _check_grid(mp, n, bm, bn, k, n_sections, section)
    idx, val = _pad_rows(idx, val, mp)
    grid = (mp // bm, n // bn, n_sections)
    out = pl.pallas_call(
        functools.partial(_kernel, section=section),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, smax), lambda i, j, s: (s, i, 0)),
            pl.BlockSpec((1, bm, smax), lambda i, j, s: (s, i, 0)),
            pl.BlockSpec((section, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(idx, val, b)
    return out[:m] if mp != m else out


# ----------------------------------------------------------------------
# Stripe-reuse variant: grid reordered to (row-tile, SECTION, col-tile) so
# the col-tile axis iterates innermost. The decompressed (bm, section)
# stripe is built once per (row-tile, section) into a VMEM scratch and
# REUSED across every col tile — the baseline order re-expands it per col
# tile. The price is an output-stationary (bm, N) row-panel accumulator
# (the out block is revisited once per section, non-consecutively, so the
# running sum must live in scratch): SpArch/Sextans-style output-stationary
# accumulation. The full VMEM footprint (panel + stripe + the idx/val/rhs
# pipeline blocks + the expansion transient) is modelled symbolically in
# ``analysis.vmem.incrs_footprint("reuse", ...)`` — that model, not a
# hand-kept formula here, is what callers (ops.spmm variant="auto", the
# autotuner's candidate prefilter) consult to fall back to the baseline
# order when the panel would not fit.


def _kernel_reuse(idx_ref, val_ref, b_ref, o_ref, stripe_ref, acc_ref, *,
                  section: int, bn: int):
    s, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _expand():
        stripe_ref[...] = _expand_stripe(idx_ref[0], val_ref[0], section)

    contrib = jnp.dot(stripe_ref[...], b_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    sl = pl.dslice(j * bn, bn)

    @pl.when(s == 0)
    def _init():
        acc_ref[:, sl] = contrib

    @pl.when(s != 0)
    def _acc():
        acc_ref[:, sl] += contrib

    @pl.when(s == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[:, sl].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("section", "bm", "bn", "interpret"))
def incrs_spmm_reuse(idx: jnp.ndarray, val: jnp.ndarray, b: jnp.ndarray, *,
                     section: int = 256, bm: int = 128, bn: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """Same contract as ``incrs_spmm`` but each section stripe is expanded
    exactly once per row tile (held in VMEM scratch) instead of once per
    (row tile, col tile): n_sections expansions per row tile vs
    n_sections * n_col_tiles."""
    n_sections, m, smax = idx.shape
    k, n = b.shape
    bm, mp = _resolve_row_tile(m, bm)      # shard-local grid bounds
    _check_grid(mp, n, bm, bn, k, n_sections, section)
    idx, val = _pad_rows(idx, val, mp)
    grid = (mp // bm, n_sections, n // bn)
    out = pl.pallas_call(
        functools.partial(_kernel_reuse, section=section, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, smax), lambda i, s, j: (s, i, 0)),
            pl.BlockSpec((1, bm, smax), lambda i, s, j: (s, i, 0)),
            pl.BlockSpec((section, bn), lambda i, s, j: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, s, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, section), jnp.float32),
                        pltpu.VMEM((bm, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(idx, val, b)
    return out[:m] if mp != m else out


# ----------------------------------------------------------------------
# Pipelined variant: one grid step per row tile. The dense RHS never
# enters the automatic Pallas pipeline — it stays in HBM (memory_space=ANY)
# and (section, bn) blocks are streamed through a double-buffered VMEM
# window by manual async copies, so block t+1 is in flight while the MXU
# contracts block t (SpArch's "stream the dense operand behind an
# output-stationary accumulator"). The (bm, N) out block is the
# accumulator itself: it is written once per (section, col-tile) step and
# leaves VMEM only when the row panel is done — partial sums never
# round-trip HBM. Stripes are still expanded once per (row-tile, section),
# and the expansion of section s+? overlaps the DMA wait for its first
# RHS block.


def _kernel_pipelined(idx_ref, val_ref, b_hbm, o_ref, b_buf, sem,
                      stripe_ref, *, section: int, bn: int, n_ct: int):
    n_sections = idx_ref.shape[0]
    total = n_sections * n_ct

    def block_copy(slot, t):
        s, j = t // n_ct, t % n_ct
        return pltpu.make_async_copy(
            b_hbm.at[pl.dslice(s * section, section), pl.dslice(j * bn, bn)],
            b_buf.at[slot], sem.at[slot])

    block_copy(0, 0).start()

    def body(t, carry):
        s, j = t // n_ct, t % n_ct

        @pl.when(t + 1 < total)
        def _prefetch():
            block_copy((t + 1) % 2, t + 1).start()

        # Expand the stripe for this section while the DMA for its first
        # RHS block is (potentially) still in flight.
        @pl.when(j == 0)
        def _expand():
            stripe_ref[...] = _expand_stripe(idx_ref[s], val_ref[s],
                                             section)

        block_copy(t % 2, t).wait()
        contrib = jnp.dot(stripe_ref[...], b_buf[t % 2].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        sl = pl.dslice(j * bn, bn)

        @pl.when(s == 0)
        def _init():
            o_ref[:, sl] = contrib

        @pl.when(s != 0)
        def _acc():
            o_ref[:, sl] += contrib

        return carry

    jax.lax.fori_loop(0, total, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("section", "bm", "bn", "interpret"))
def incrs_spmm_pipelined(idx: jnp.ndarray, val: jnp.ndarray,
                         b: jnp.ndarray, *, section: int = 256,
                         bm: int = 128, bn: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """Same contract as ``incrs_spmm``; RHS is double-buffered from HBM.

    The per-row-tile VMEM footprint (out panel, stripe, the 2-deep RHS
    stream window, idx/val pipeline blocks, expansion transient) is
    modelled term-by-term in ``analysis.vmem.incrs_footprint("pipelined",
    ...)``; callers (``ops.spmm``/autotuner) consult that model and fall
    back to the baseline order when the panel would not fit. The dot
    shape and section accumulation order match the other variants
    exactly, so outputs are bitwise identical at equal (bm, bn).
    """
    n_sections, m, smax = idx.shape
    k, n = b.shape
    bm, mp = _resolve_row_tile(m, bm)
    _check_grid(mp, n, bm, bn, k, n_sections, section)
    idx, val = _pad_rows(idx, val, mp)
    n_ct = n // bn
    out = pl.pallas_call(
        functools.partial(_kernel_pipelined, section=section, bn=bn,
                          n_ct=n_ct),
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((n_sections, bm, smax), lambda i: (0, i, 0)),
            pl.BlockSpec((n_sections, bm, smax), lambda i: (0, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, section, bn), b.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((bm, section), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(idx, val, b)
    return out[:m] if mp != m else out
