"""Distributed reduction + solvers on the union path (exact SPMD semantics)."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't error
from hypothesis import given, settings, strategies as st

from repro.core import distributed as D
from repro.core import partition as part
from repro.core import sequential as seq
from repro.core import solvers as S
from repro.core.bitset_mwis import mwis_exact
from repro.graphs import generators as gen
from tests.helpers import MED_PAD, SMALL_PAD, residual_exact_weight


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 100_000), st.sampled_from([2, 4]),
       st.sampled_from(["sync", "async"]))
def test_distributed_reduce_preserves_alpha(seed, p, mode):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 13))
    g = gen.random_graph(n, float(rng.uniform(0.1, 0.7)), seed=seed)
    best, _ = mwis_exact(g)
    pg = part.partition_graph(g, p, window_cap=8, common_cap=4,
                              pad_to=SMALL_PAD)
    cfg = D.DisReduConfig(heavy_k=6, mode=mode, max_rounds=200)
    state, prob, rounds = D.disredu(pg, cfg)
    wgt, indep = residual_exact_weight(g, pg, state, prob)
    assert indep and wgt == best
    assert rounds < 200


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 100_000), st.sampled_from([1, 3, 4]))
def test_greedy_equals_sequential_oracle(seed, p):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 40))
    g = gen.random_graph(n, 0.15, seed=seed)
    if seed % 2:  # force weight ties
        g = type(g)(indptr=g.indptr, indices=g.indices,
                    weights=(g.weights % 3 + 1).astype(np.int32))
    want, _ = seq.solve_greedy(g)
    pg = part.partition_graph(g, p, window_cap=8, pad_to=MED_PAD)
    members, _, _ = S.solve(pg, "greedy")
    assert g.is_independent_set(members)
    assert g.set_weight(members) == want


@pytest.mark.parametrize("algo", ["rg", "rnp"])
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_solvers_complete_and_sound(algo, mode):
    for seed in range(3):
        g = gen.rhg_like(250, avg_deg=6, seed=seed)
        pg = part.partition_graph(g, 4, window_cap=12)
        members, state, _ = S.solve(
            pg, algo, D.DisReduConfig(heavy_k=6, mode=mode)
        )
        assert g.is_independent_set(members)
        assert g.set_weight(members) > 0


def test_rnp_quality_close_to_sequential():
    """Paper Table 7.1 analogue: distributed RnP stays within a few % of
    the sequential reduce-and-peel baseline."""
    ratios = []
    for seed in range(4):
        g = gen.rhg_like(300, avg_deg=6, seed=seed)
        w_seq, _ = seq.solve_reduce_and_peel(g)
        pg = part.partition_graph(g, 4, window_cap=12)
        members, _, _ = S.solve(
            pg, "rnp", D.DisReduConfig(heavy_k=6, mode="async")
        )
        ratios.append(g.set_weight(members) / max(w_seq, 1))
    assert np.mean(ratios) > 0.93, ratios


def test_reduction_impact_worsens_mildly_with_p():
    """Paper Fig 7.1: kernel size grows with p but stays bounded."""
    g = gen.rgg2d(2000, avg_deg=8, seed=0)
    sizes = {}
    for p in (1, 4, 8):
        pg = part.partition_graph(g, p, window_cap=12)
        cfg = D.DisReduConfig(heavy_k=8, mode="sync")
        state, prob, _ = D.disredu(pg, cfg)
        nv, ne = D.kernel_stats(pg, state)
        sizes[p] = nv / g.n
    assert sizes[4] >= sizes[1] - 1e-9
    assert sizes[8] <= sizes[1] + 0.30  # stays bounded (paper: ~+10% median)


def test_async_matches_sync_fixpoint_quality():
    g = gen.rgg2d(800, avg_deg=8, seed=1)
    res = {}
    for mode in ("sync", "async"):
        pg = part.partition_graph(g, 4, window_cap=12)
        state, prob, _ = D.disredu(pg, D.DisReduConfig(mode=mode))
        res[mode] = D.kernel_stats(pg, state)
    # both reach a fixpoint of the same rule family; sizes should be close
    nv_s, nv_a = res["sync"][0], res["async"][0]
    assert abs(nv_s - nv_a) <= 0.1 * max(nv_s, nv_a, 1)
