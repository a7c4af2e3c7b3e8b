"""Pallas flash attention (GQA, causal/windowed) — MXU-tiled online softmax.

The LM framework's hottest kernel, built on the same principle the paper's
synchronized mesh uses for SpMM: stream one operand (keys/values) past
resident state (the query tile + running softmax statistics) in fixed-size
rounds, never materializing the full score matrix. The K-loop is the grid's
innermost dimension; m/l/acc live in VMEM scratch across its iterations —
the direct analogue of Alg. 2's per-node buffers carried across rounds.

Layout: q (L, Sq, hd) with L = B*KV*G flattened lanes; k/v (Lk, Sk, hd)
with Lk = B*KV (the kernel indexes k by lane // G: GQA sharing without
materializing repeated heads). Causal/window masking is positional, so
padded tails are masked out naturally (pad positions < 0).

Statically verified: ``analysis.vmem.flash_footprint`` models this
launch term-for-term (scratch signature drift-guarded against
``vmem.EXPECTED_SCRATCH``), and the grid abstract interpreter
(``analysis.grid_interp``) proves bounds, m/l/acc init+flush
discipline, output coverage and parallel-axis race-freedom for
``_kernel`` in CI — safe because only the "arbitrary" K axis carries
scratch state; the two "parallel" axes are pure tilings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, sk: int, window, scale: float, soft_cap):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos <= qpos
    if window is not None:
        valid &= kpos > qpos - window
    valid &= kpos < sk

    # Skip fully-masked K blocks (below the causal diagonal / outside the
    # window) — the "only useful computation" rule at block granularity.
    first_useful = 0 if window is None else \
        jnp.maximum(0, (qi * bq - window) // bk)
    useful = (ki * bk <= qi * bq + bq - 1)
    if window is not None:
        useful &= (ki >= first_useful)

    @pl.when(useful)
    def _compute():
        logits = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if soft_cap:
            logits = soft_cap * jnp.tanh(logits / soft_cap)
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("g", "window", "soft_cap", "bq", "bk",
                              "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    g: int, window=None, soft_cap=None, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (L, Sq, hd) L = B*KV*G query lanes; k/v: (L//g, Sk, hd).
    Sq/Sk padded to bq/bk multiples by the wrapper (ops.flash_mha)."""
    lanes, sq, hd = q.shape
    lk, sk, _ = k.shape
    if lanes != lk * g:
        raise ValueError(f"query lanes {lanes} != kv lanes {lk} * g={g}")
    if sq % bq or sk % bk:
        raise ValueError(f"seq lens {(sq, sk)} must align to tiles "
                         f"{(bq, bk)} (ops.flash_mha pads)")
    grid = (lanes, sq // bq, sk // bk)
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, sk=sk, window=window,
        scale=1.0 / np.sqrt(hd), soft_cap=soft_cap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, qi, ki: (h // g, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, qi, ki: (h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((lanes, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
