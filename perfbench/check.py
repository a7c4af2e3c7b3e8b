"""The comparison that decides ``correct``, and its control.

The plain reference is the dense float32 product of the operand the
configuration's generator made, at ``HIGHEST`` matmul precision, computed
on the device in blocks of ``BLOCK`` columns after the window has closed and
the program's state is freed. It imports nothing of the program.

For each compared request the number is its widest gap relative to its
scale: max |served - reference| / max |reference| over the request's output
panel. A run's number is the largest over the compared requests.

The control puts the reference, computed in a lower precision, in the
program's place: ``fp8`` rounds both operands to float8 e4m3 with one scale
per operand block, the step below the bfloat16 products the chip's MXU
makes of float32 operands at default precision. ``bf16`` rounds both
operands to bfloat16 and accumulates in float32, which is that default
precision itself: it is reported beside the program, not used as a limit.
"""
from __future__ import annotations

import functools
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024
MODES = ("program", "fp8", "bf16")
_HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def seed_key(seed: int):
    """A PRNG key for any whole seed, also those past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@functools.partial(jax.jit, static_argnames=("mode",))
def _block_gaps(a, b, out, mode: str):
    ref = jnp.dot(a, b, precision=_HIGHEST)
    if mode == "program":
        got = out
    elif mode == "fp8":
        got = jnp.dot(_fp8(a), _fp8(b), precision=_HIGHEST)
    elif mode == "bf16":
        got = jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return (jnp.max(jnp.abs(got - ref), axis=0),
            jnp.max(jnp.abs(ref), axis=0))


def gaps(a, items: Sequence[Tuple[np.ndarray, Optional[np.ndarray]]],
         mode: str = "program") -> List[float]:
    """Relative gap of each ``(b, served)`` item. ``a`` is the dense
    operand on the device; ``served`` may be None for a control mode."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    m, k = a.shape
    widths = [b.shape[1] for b, _ in items]
    total = sum(widths)
    n_blocks = -(-total // BLOCK)
    diff = np.zeros(n_blocks * BLOCK, np.float64)
    scale = np.zeros(n_blocks * BLOCK, np.float64)
    starts = np.concatenate([[0], np.cumsum(widths)])
    for blk in range(n_blocks):
        lo, hi = blk * BLOCK, (blk + 1) * BLOCK
        b_blk = np.zeros((k, BLOCK), np.float32)
        o_blk = np.zeros((m, BLOCK), np.float32) if mode == "program" \
            else np.zeros((1, 1), np.float32)
        for (b, out), s0 in zip(items, starts[:-1]):
            a0, a1 = max(lo, s0), min(hi, s0 + b.shape[1])
            if a1 <= a0:
                continue
            b_blk[:, a0 - lo:a1 - lo] = b[:, a0 - s0:a1 - s0]
            if mode == "program":
                o_blk[:, a0 - lo:a1 - lo] = out[:, a0 - s0:a1 - s0]
        d, s = _block_gaps(a, jnp.asarray(b_blk), jnp.asarray(o_blk), mode)
        diff[lo:hi], scale[lo:hi] = np.asarray(d), np.asarray(s)
    out = []
    for s0, s1 in zip(starts[:-1], starts[1:]):
        den = float(scale[s0:s1].max()) if s1 > s0 else 0.0
        num = float(diff[s0:s1].max()) if s1 > s0 else 0.0
        out.append(num / den if den > 0 else (0.0 if num == 0 else np.inf))
    return out


class Sampler:
    """Keeps the served panels of a seeded sample of finished requests
    and of the widest one, and lets every other panel go.

    Each request gets a priority drawn from the seed and its identity; the
    ``n - 1`` lowest priorities among the finished requests are kept, so the
    sample depends on which requests finished, not on their order."""

    def __init__(self, seed: int, n: int):
        self.seed, self.n = int(seed), max(1, int(n))
        self._heap: List[Tuple[float, tuple]] = []     # (-priority, ident)
        self.kept: Dict[tuple, tuple] = {}             # ident -> (b, out)
        self._widest: Optional[tuple] = None

    def _priority(self, ident: tuple) -> float:
        return float(np.random.default_rng(
            [self.seed, 7919] + [i + 1 for i in ident]).random())

    def offer(self, ident: tuple, b: np.ndarray, out: np.ndarray) -> None:
        self.kept[ident] = (b, out)
        in_heap = False
        if self.n > 1:
            p = self._priority(ident)
            if len(self._heap) < self.n - 1:
                heapq.heappush(self._heap, (-p, ident))
                in_heap = True
            elif p < -self._heap[0][0]:
                _, gone = heapq.heapreplace(self._heap, (-p, ident))
                in_heap = True
                if gone != self._widest:
                    self.kept.pop(gone, None)
        old = self._widest
        if old is None or b.shape[1] > self.kept[old][0].shape[1]:
            self._widest = ident
            if old is not None and not self._in_heap(old):
                self.kept.pop(old, None)
        elif not in_heap:
            self.kept.pop(ident, None)

    def _in_heap(self, ident: tuple) -> bool:
        return any(i == ident for _, i in self._heap)

    def items(self) -> List[Tuple[tuple, tuple]]:
        return sorted(self.kept.items())
