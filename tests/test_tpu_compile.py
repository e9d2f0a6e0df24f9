"""Compiles for a described TPU v5e chip — no chip attached.

Mosaic compiles the fused aggregate kernel here exactly as it would on the
chip, so a kernel the TPU compiler refuses (an unsupported matmul dtype, a
block shape off the (8, 128) tiling, too much VMEM) fails in this file
instead of on the chip.  The topology is described inside a module fixture
(never at import: one process at a time may load the TPU library).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine as E
from repro.kernels.segment_coo import ops
from repro.kernels.segment_coo.kernel import segment_fused_planar

#: (n_blocks, E_BLK, R_BLK, window cap D) of the blocked-ELL plans: the
#: serve_m cell, and GNM n=2^20 m=2^22 on one PE (weak_1m, r_blk 32).
PLANS = {"serve_m": (33, 320, 32, 8), "weak_1m": (32769, 456, 32, 16)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it, and give the rest of
    # the worker's tests the cache setting they started with
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.mark.parametrize("cell", sorted(PLANS))
def test_fused_kernel_compiles_for_v5e(one_chip, cell):
    """Sum + max + min + OR payloads through Mosaic at the plan shapes."""
    nb, e_blk, r_blk, d = PLANS[cell]
    fn = functools.partial(segment_fused_planar, r_blk=r_blk, or_nbits=d)
    compiled = jax.jit(
        lambda s, mx, mn, o, lrow: fn(s, mx, mn, lrow, data_or=o)
    ).lower(
        _i32(one_chip, 2, nb, e_blk), _i32(one_chip, 2, nb, e_blk),
        _i32(one_chip, 1, nb, e_blk), _i32(one_chip, 2, nb, e_blk),
        _i32(one_chip, nb, e_blk),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_edge_major_wrapper_compiles_for_v5e(one_chip, monkeypatch):
    """segment_fused_coo: edge-major [E, D] payloads gathered into the
    kernel's payload-major blocks, all four payload groups."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    nb, e_blk, r_blk, d = PLANS["serve_m"]
    n_edges = nb * e_blk // 2

    def agg(perm, lrow, s, mx, mn, o):
        return ops.segment_fused_coo(
            perm, lrow, nb * r_blk, data_sum=s, data_max=mx, data_min=mn,
            data_or=o, or_nbits=d, r_blk=r_blk, force_pallas=True)

    compiled = jax.jit(agg).lower(
        _i32(one_chip, nb, e_blk), _i32(one_chip, nb, e_blk),
        _i32(one_chip, n_edges, 2), _i32(one_chip, n_edges, 2),
        _i32(one_chip, n_edges, 1), _i32(one_chip, n_edges, 2),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cell", sorted(PLANS))
def test_engine_aggregate_pallas_compiles_for_v5e(one_chip, monkeypatch,
                                                  cell):
    """engine.aggregate with the cheap-fused schedule's payload set (S, deg
    sums; M, only maxes; window bits ORs) lowers to the Mosaic kernel."""
    # jax.default_backend() is the CPU here; steer the wrapper to the
    # compiled kernel the chip would run
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    nb, e_blk, r_blk, d = PLANS[cell]
    v = nb * r_blk
    n_edges = nb * e_blk // 2
    plan = E.SegPlan(
        edge_perm=_i32(one_chip, nb, e_blk), lrow=_i32(one_chip, nb, e_blk),
        rblk_tpl=_i32(one_chip, r_blk, 0),
        wbits=_i32(one_chip, n_edges), wnh=_i32(one_chip, n_edges),
    )

    def agg(row, dsum, dmax, dor, plan):
        return E.aggregate(row, v, data_sum=dsum, data_max=dmax,
                           data_or=dor, or_nbits=d, backend="pallas",
                           plan=plan)

    compiled = jax.jit(agg).lower(
        _i32(one_chip, n_edges), _i32(one_chip, n_edges, 2),
        _i32(one_chip, n_edges, 2), _i32(one_chip, n_edges, 2), plan,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
