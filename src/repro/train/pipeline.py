"""GPipe-style pipeline parallelism over a "pipe" mesh axis.

For >4k-chip scaling the (data, model) mesh runs out of useful parallel
axes; this module adds a collective-permute pipeline: stages hold disjoint
layer groups, microbatches flow stage-to-stage via ``jax.lax.ppermute``
inside ``shard_map``. The schedule is classic GPipe (fill, steady state,
drain: T = n_micro + n_stages - 1 steps). The whole pipeline is
differentiable — JAX transposes ppermute/scan, so ``jax.grad`` through
``pipeline_apply`` yields the reverse-schedule backward pass automatically.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x, *, n_stages: int,
                   n_micro: int, mesh: Mesh, axis: str = "pipe"):
    """Run ``x`` through ``n_stages`` sequential stages on the mesh.

    stage_fn      : (params_one_stage, h) -> h, identical signature/shape
    stage_params  : pytree whose leaves have leading dim n_stages
    x             : (n_micro, mb, ...) microbatched input (replicated)

    Returns (n_micro, mb, ...) outputs of the final stage (replicated).
    """
    t_total = n_micro + n_stages - 1

    def local(params_local, xloc):
        # params_local: leaves (1, ...) — this device's stage params.
        params1 = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xloc[0])
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(h, t):
            inject = xloc[jnp.clip(t, 0, n_micro - 1)]
            h_in = jnp.where(stage == 0, inject, h)
            out = stage_fn(params1, h_in)
            y = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
            h_next = jax.lax.ppermute(out, axis, perm)
            return h_next, y

        _, ys = jax.lax.scan(step, zero, jnp.arange(t_total))
        # microbatch m exits the last stage at t = m + n_stages - 1
        outs = ys[n_stages - 1:]
        # broadcast final-stage outputs to every pipe rank
        outs = jax.lax.psum(outs, axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return shard_map(
        local, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(), check_vma=False,
    )(stage_params, x)


def split_stages(stacked_params, n_stages: int):
    """Reshape scan-stacked layer params (n_layers_groups, ...) into
    (n_stages, groups_per_stage, ...) for the pipeline executor."""
    def r(a):
        g = a.shape[0]
        if g % n_stages != 0:
            raise ValueError(f"{g} layer groups do not divide into "
                             f"{n_stages} pipeline stages")
        return a.reshape(n_stages, g // n_stages, *a.shape[1:])
    return jax.tree.map(r, stacked_params)


def incrs_stage_fn(act: Callable = jnp.tanh) -> Callable:
    """Stage function over a shared-pattern stack (``sparse.stack_init`` —
    a ``sparse.Linear`` whose values leaf carries a leading stage axis):
    each stage applies the fused InCRS SpMM (custom VJP, so ``jax.grad``
    through ``pipeline_apply`` yields the reverse-schedule backward on the
    same sparse kernels) followed by ``act``. Works with raw
    ``InCRSLinearParams`` stacks too — ``sparse.apply`` dispatches both
    through the format registry.

    Only the ``values`` leaf carries a stage axis; the stripe metadata is
    pytree aux data shared by every stage, which is exactly what the
    per-stage ``leaf[0]`` slicing and the ``P(axis)`` param specs above
    require — per-stage patterns would need per-stage static metadata and
    cannot ride one ``shard_map``.
    """
    from ..sparse import api

    def stage(params_one_stage, h):
        return act(api.apply(params_one_stage, h))
    return stage
