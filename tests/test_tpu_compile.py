"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jax and compiles for a topology it is
only told about, so what Mosaic would refuse on the chip (block shapes off
the (8, 128) tiling, gathers it cannot lower, too much VMEM) fails here.
Shapes are those of ``chip_smoke.py``: Phase B's sparse layer (the
Mixtral-8x7B expert FFN, 4096 -> 14336 at density 0.5, 512 tokens) and
Phase A's docword operand for the BSR kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsr_spmm import bsr_spmm
from repro.kernels.dense_mm import dense_mm
from repro.kernels.incrs_gather import incrs_gather
from repro.kernels.incrs_spmm import (incrs_spmm, incrs_spmm_pipelined,
                                      incrs_spmm_reuse)
from repro.kernels.index_match_spmm import index_match_spmm
from repro.spgemm.kernels import spgemm_condense, spgemm_merge

# Phase B forward: W^T stripes (sections, d_out, smax) against x^T.
SECTION, N_SECTIONS, D_OUT, SMAX, TOKENS = 256, 16, 14336, 160, 512
# Phase A's BSR wave: docword padded to 64-tiles, one 1024-col wave.
BSR_ROWS, BSR_K, BSR_BLOCK, BSR_COLS = 704, 12032, 64, 1024
# Round-prepped CRS operands for the index-match and SpGEMM kernels.
ROUNDS, N_ROUNDS, RMAX, M_CRS, N_CRS = 128, 8, 16, 512, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:         # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_has_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", [incrs_spmm, incrs_spmm_reuse,
                                    incrs_spmm_pipelined],
                         ids=["expand", "reuse", "pipelined"])
def test_incrs_kernels_compile_at_phase_b_width(one_chip, kernel):
    _compile_has_kernel(
        lambda i, v, b: kernel(i, v, b, section=SECTION, bm=128, bn=512),
        _sds(one_chip, (N_SECTIONS, D_OUT, SMAX), jnp.int32),
        _sds(one_chip, (N_SECTIONS, D_OUT, SMAX), jnp.float32),
        _sds(one_chip, (N_SECTIONS * SECTION, TOKENS), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_dense_mm_compiles_at_phase_b_width(one_chip, dtype):
    _compile_has_kernel(
        lambda a, b: dense_mm(a, b),
        _sds(one_chip, (D_OUT, N_SECTIONS * SECTION), dtype),
        _sds(one_chip, (N_SECTIONS * SECTION, TOKENS), dtype))


def test_bsr_spmm_compiles_at_phase_a_width(one_chip):
    n_blocks = (BSR_ROWS // BSR_BLOCK) * 32
    _compile_has_kernel(
        lambda r, c, v, b: bsr_spmm(r, c, v, b,
                                    n_block_rows=BSR_ROWS // BSR_BLOCK,
                                    bn=512),
        _sds(one_chip, (n_blocks + 1,), jnp.int32),
        _sds(one_chip, (n_blocks,), jnp.int32),
        _sds(one_chip, (n_blocks, BSR_BLOCK, BSR_BLOCK), jnp.float32),
        _sds(one_chip, (BSR_K, BSR_COLS), jnp.float32))


def test_incrs_gather_compiles(one_chip):
    _compile_has_kernel(
        lambda i, v: incrs_gather(i, v, section=SECTION, bm=128),
        _sds(one_chip, (N_SECTIONS, 1024, SMAX), jnp.int32),
        _sds(one_chip, (N_SECTIONS, 1024, SMAX), jnp.float32))


def _round_operands(sharding):
    return (_sds(sharding, (N_ROUNDS, M_CRS, RMAX), jnp.int32),
            _sds(sharding, (N_ROUNDS, M_CRS, RMAX), jnp.float32),
            _sds(sharding, (N_ROUNDS, N_CRS, RMAX), jnp.int32),
            _sds(sharding, (N_ROUNDS, N_CRS, RMAX), jnp.float32))


@pytest.mark.parametrize("kernel", [index_match_spmm, spgemm_condense],
                         ids=["index_match", "condense"])
def test_round_kernels_compile(one_chip, kernel):
    _compile_has_kernel(
        lambda ai, av, bi, bv: kernel(ai, av, bi, bv, rounds=ROUNDS,
                                      bm=128, bn=128),
        *_round_operands(one_chip))


def test_spgemm_merge_compiles(one_chip):
    _compile_has_kernel(
        lambda s: spgemm_merge(s, bm=128, bn=128),
        _sds(one_chip, (N_ROUNDS, M_CRS, N_CRS), jnp.float32))
