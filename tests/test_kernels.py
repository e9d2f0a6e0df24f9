"""Pallas kernels: interpret-mode execution vs jnp oracles, shape sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag.kernel import embedding_bag_fused
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.segment_coo.kernel import (
    segment_fused_planar, segment_sum_blocked,
)
from repro.kernels.segment_coo.ops import (
    pack_blocks, pack_blocks_stacked, segment_fused_coo, segment_sum_coo,
)
from repro.kernels.segment_coo.ref import (
    segment_fused_blocked_ref, segment_sum_blocked_ref,
)
from repro.kernels.wedge_intersect.kernel import wedge_intersect
from repro.kernels.wedge_intersect.ops import common_neighbor_stats
from repro.kernels.wedge_intersect.ref import wedge_intersect_ref


@pytest.mark.parametrize("n_rows,n_edges,d,r_blk", [
    (17, 120, 8, 8), (64, 9, 128, 8), (5, 64, 16, 4), (33, 257, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_coo_kernel_matches_ref(n_rows, n_edges, d, r_blk, dtype):
    rng = np.random.default_rng(0)
    row = rng.integers(0, n_rows, size=n_edges).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(n_edges, d)), dtype)
    edge_perm, lrow, e_blk = pack_blocks(row, n_rows, r_blk=r_blk)
    blocked = data[jnp.asarray(edge_perm.reshape(-1))].reshape(
        edge_perm.shape[0], e_blk, d
    )
    out_k = segment_sum_blocked(
        blocked, jnp.asarray(lrow), r_blk=r_blk, interpret=True
    )
    out_r = segment_sum_blocked_ref(blocked, jnp.asarray(lrow), r_blk=r_blk)
    # bf16: kernel accumulates in f32 via the MXU (preferred_element_type);
    # the jnp ref rounds per-add — kernel is the more accurate of the two
    tol = 1e-6 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol,
    )
    # end-to-end wrapper matches the canonical segment_sum
    got = segment_sum_coo(
        data, jnp.asarray(edge_perm), jnp.asarray(lrow), n_rows,
        r_blk=r_blk, force_pallas=True,
    )
    want = jax.ops.segment_sum(data, jnp.asarray(row), num_segments=n_rows)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("n_rows,n_edges,r_blk", [
    # n_rows > n_edges leaves empty segments → exercises the identities
    (17, 120, 8), (64, 9, 8), (33, 257, 16),
])
def test_segment_fused_kernel_matches_ref_int32(n_rows, n_edges, r_blk):
    """Fused sum+max+min (interpret mode) == blocked ref == jax.ops, exactly
    (int payloads — the aggregate-engine contract is bit-identity)."""
    rng = np.random.default_rng(3)
    row = rng.integers(0, n_rows, size=n_edges).astype(np.int32)
    dsum = jnp.asarray(rng.integers(-500, 500, size=(n_edges, 2)), jnp.int32)
    dmax = jnp.asarray(rng.integers(-500, 500, size=(n_edges, 2)), jnp.int32)
    dmin = jnp.asarray(rng.integers(-500, 500, size=(n_edges, 1)), jnp.int32)
    edge_perm, lrow, e_blk = pack_blocks(row, n_rows, r_blk=r_blk)

    def blocked(d):
        return d[jnp.asarray(edge_perm.reshape(-1))].reshape(
            edge_perm.shape[0], e_blk, d.shape[-1]
        )

    # the kernel takes payload-major [D, nb, E_BLK] blocks, the reference
    # edge-major [nb, E_BLK, D]
    out_k = segment_fused_planar(
        *(jnp.moveaxis(blocked(d), 2, 0) for d in (dsum, dmax, dmin)),
        jnp.asarray(lrow), r_blk=r_blk, interpret=True,
    )
    out_r = segment_fused_blocked_ref(
        blocked(dsum), blocked(dmax), blocked(dmin), jnp.asarray(lrow),
        r_blk=r_blk,
    )
    for k, r in zip(out_k[:3], out_r):
        np.testing.assert_array_equal(np.moveaxis(np.asarray(k), 0, 2),
                                      np.asarray(r))
    # end-to-end wrapper (pallas-interpret) == canonical jax.ops semantics
    got = segment_fused_coo(
        jnp.asarray(edge_perm), jnp.asarray(lrow), n_rows,
        data_sum=dsum, data_max=dmax, data_min=dmin,
        r_blk=r_blk, force_pallas=True,
    )
    seg = jnp.asarray(row)
    want = (
        jax.ops.segment_sum(dsum, seg, num_segments=n_rows),
        jax.ops.segment_max(dmax, seg, num_segments=n_rows),
        jax.ops.segment_min(dmin, seg, num_segments=n_rows),
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", ["negative", "wrapping"])
def test_segment_fused_sum_int32_edges(case):
    """The kernel's limb-split MXU sum is exact over the whole int32 range:
    negative payloads, and segment sums that wrap past the int32 limits
    exactly as jax.ops.segment_sum does (folded weights reach them)."""
    rng = np.random.default_rng(21)
    n_rows, n_edges, r_blk = 37, 300, 8
    row = rng.integers(0, n_rows, size=n_edges).astype(np.int32)
    info = np.iinfo(np.int32)
    if case == "negative":
        dsum = rng.integers(info.min, 0, size=(n_edges, 2))
    else:
        dsum = rng.choice(
            [info.max, info.max - 1, info.min, info.min + 1, 1 << 30, -1],
            size=(n_edges, 2),
        )
    dsum = jnp.asarray(dsum.astype(np.int32))
    dor = jnp.asarray(rng.integers(0, 1 << 16, size=(n_edges, 1)), jnp.int32)
    edge_perm, lrow, _ = pack_blocks(row, n_rows, r_blk=r_blk)
    s, _, _, o = segment_fused_coo(
        jnp.asarray(edge_perm), jnp.asarray(lrow), n_rows,
        data_sum=dsum, data_or=dor, r_blk=r_blk, force_pallas=True,
    )
    want = jax.ops.segment_sum(dsum, jnp.asarray(row), num_segments=n_rows)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(want))
    want_or = np.zeros((n_rows, 1), np.int32)
    for e in range(n_edges):
        want_or[row[e]] |= np.asarray(dor)[e]
    np.testing.assert_array_equal(np.asarray(o), want_or)


def test_segment_fused_partial_payloads_and_ref_dispatch():
    """Absent payload groups come back as None on both dispatch paths."""
    rng = np.random.default_rng(4)
    n_rows, n_edges = 23, 77
    row = rng.integers(0, n_rows, size=n_edges).astype(np.int32)
    dmax = jnp.asarray(rng.integers(0, 100, size=(n_edges, 3)), jnp.int32)
    edge_perm, lrow, _ = pack_blocks(row, n_rows, r_blk=8)
    want = jax.ops.segment_max(dmax, jnp.asarray(row), num_segments=n_rows)
    for force in (True, False):
        s, m, n, o = segment_fused_coo(
            jnp.asarray(edge_perm), jnp.asarray(lrow), n_rows,
            data_max=dmax, force_pallas=force,
        )
        assert s is None and n is None and o is None
        np.testing.assert_array_equal(np.asarray(m), np.asarray(want))


@pytest.mark.parametrize("n_rows,n_edges,r_blk,nbits", [
    (17, 120, 8, 12), (33, 257, 16, 16), (64, 9, 8, 5),
])
def test_segment_fused_or_payloads(n_rows, n_edges, r_blk, nbits):
    """Bitwise-OR payload group (kernel bitplane matmul + blocked ref + the
    generic jnp fallback) == per-segment np.bitwise_or, exactly."""
    from repro.kernels.segment_coo.ref import segment_or_ref

    rng = np.random.default_rng(11)
    row = rng.integers(0, n_rows, size=n_edges).astype(np.int32)
    dor = rng.integers(0, 1 << nbits, size=(n_edges, 2)).astype(np.int32)
    dsum = rng.integers(-9, 9, size=(n_edges, 1)).astype(np.int32)
    edge_perm, lrow, _ = pack_blocks(row, n_rows, r_blk=r_blk)
    want = np.zeros((n_rows, 2), np.int32)
    for e in range(n_edges):
        want[row[e]] |= dor[e]
    for force in (True, False):
        s, _, _, o = segment_fused_coo(
            jnp.asarray(edge_perm), jnp.asarray(lrow), n_rows,
            data_sum=jnp.asarray(dsum), data_or=jnp.asarray(dor),
            or_nbits=nbits, r_blk=r_blk, force_pallas=force,
        )
        np.testing.assert_array_equal(np.asarray(o), want)
        np.testing.assert_array_equal(
            np.asarray(s),
            np.asarray(jax.ops.segment_sum(
                jnp.asarray(dsum), jnp.asarray(row), num_segments=n_rows
            )),
        )
    got = segment_or_ref(
        jnp.asarray(dor), jnp.asarray(row), n_rows, nbits=nbits
    )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pack_blocks_stacked_shared_budget():
    """Stacked packing pads every PE to one shared E_BLK and each PE's plan
    reproduces its own per-PE packing semantics."""
    rng = np.random.default_rng(5)
    p, E, n_rows = 3, 64, 19
    rows = rng.integers(0, n_rows, size=(p, E)).astype(np.int32)
    perm, lrow, e_blk = pack_blocks_stacked(rows, n_rows, r_blk=8)
    n_blocks = (n_rows + 8 - 1) // 8
    assert perm.shape == lrow.shape == (p, n_blocks, e_blk)
    for i in range(p):
        data = jnp.asarray(
            rng.integers(-9, 9, size=(E, 1)), jnp.int32
        )
        got, _, _, _ = segment_fused_coo(
            jnp.asarray(perm[i]), jnp.asarray(lrow[i]), n_rows,
            data_sum=data, force_pallas=False,
        )
        want = jax.ops.segment_sum(
            data, jnp.asarray(rows[i]), num_segments=n_rows
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("E,D,e_blk", [(100, 8, 32), (513, 16, 256), (7, 4, 8)])
def test_wedge_intersect_kernel_matches_ref(E, D, e_blk):
    rng = np.random.default_rng(1)
    V = 50
    wu = rng.integers(0, V + 1, size=(E, D)).astype(np.int32)
    wv = rng.integers(0, V + 1, size=(E, D)).astype(np.int32)
    awu = rng.integers(0, 200, size=(E, D)).astype(np.int32)
    actu = rng.integers(0, 2, size=(E, D)).astype(np.int32)
    c_k, k_k = wedge_intersect(
        jnp.asarray(wu), jnp.asarray(wv), jnp.asarray(awu),
        jnp.asarray(actu), e_blk=e_blk, interpret=True,
    )
    c_r, k_r = wedge_intersect_ref(
        jnp.asarray(wu), jnp.asarray(wv), jnp.asarray(awu), jnp.asarray(actu)
    )
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(k_k), np.asarray(k_r))


def test_wedge_ops_counts_common_neighbors():
    """C/K from the ops wrapper equal a direct set computation."""
    from repro.core import partition as part
    from repro.graphs import generators as gen

    g = gen.random_graph(30, 0.3, seed=7)
    pg = part.partition_graph(g, 1, window_cap=8)
    window = jnp.asarray(pg.window[0])
    weights = jnp.asarray(pg.w0[0])
    active = jnp.asarray(pg.is_local[0] | pg.is_ghost[0])
    row = jnp.asarray(pg.row[0])
    col = jnp.asarray(pg.col[0])
    c, k = common_neighbor_stats(
        window, weights, active, row, col, force_pallas=True
    )
    c = np.asarray(c)
    for e in range(pg.E):
        r, cc = int(pg.row[0, e]), int(pg.col[0, e])
        if r == pg.nil:
            continue
        nr = set(g.neighbors(int(pg.gid[0, r])).tolist())
        nc = set(g.neighbors(int(pg.gid[0, cc])).tolist())
        common = nr & nc
        if g.degree(int(pg.gid[0, r])) <= 8 and g.degree(int(pg.gid[0, cc])) <= 8:
            want = sum(int(g.weights[x]) for x in common)
            assert c[e] == want, e


@pytest.mark.parametrize("V,B,K,D,b_blk", [
    (100, 33, 4, 16, 8), (64, 8, 1, 128, 4), (500, 70, 7, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_kernel_matches_ref(V, B, K, D, b_blk, dtype):
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(size=(V, D)), dtype)
    idx = jnp.asarray(rng.integers(0, V, size=(B, K)), jnp.int32)
    wgt = jnp.asarray(rng.normal(size=(B, K)), jnp.float32)
    out_k = embedding_bag_fused(table, idx, wgt, b_blk=b_blk, interpret=True)
    out_r = embedding_bag_ref(table, idx, wgt)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol,
    )
