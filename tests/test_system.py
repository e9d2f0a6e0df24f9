"""End-to-end system behaviour: the paper's full pipeline, input → solution.

The central property (the paper's Theorems 4.x composed): for any graph and
any PE count, DisRedu{S,A} + residual solve + reconstruction yields an
independent set whose weight equals the exact MWIS weight.
"""

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
from tests.helpers import SMALL_PAD, residual_exact_weight


@settings(max_examples=30, deadline=None)
@given(
    st.integers(0, 1_000_000),
    st.sampled_from([1, 2, 4]),
    st.sampled_from(["sync", "async"]),
)
def test_end_to_end_reduction_is_exact(seed, p, mode):
    """reduce → exact residual → reconstruct == brute force, any p/mode."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 13))
    g = gen.random_graph(n, float(rng.uniform(0.1, 0.75)), seed=seed)
    best, _ = mwis_exact(g)
    pg = part.partition_graph(g, p, window_cap=8, common_cap=4,
                              pad_to=SMALL_PAD)
    state, prob, _ = D.disredu(
        pg, D.DisReduConfig(heavy_k=6, mode=mode, max_rounds=300)
    )
    wgt, indep = residual_exact_weight(g, pg, state, prob)
    assert indep
    assert wgt == best


def test_full_pipeline_on_weak_scaling_families():
    """GNM barely reduces, RGG partially, RHG strongly (paper Table C.4)."""
    impact = {}
    for name in ("gnm", "rgg", "rhg"):
        g = gen.FAMILIES[name](1500, seed=0)
        pg = part.partition_graph(g, 4, window_cap=12)
        state, prob, _ = D.disredu(pg, D.DisReduConfig(heavy_k=8))
        nv, _ = D.kernel_stats(pg, state)
        impact[name] = nv / g.n
    assert impact["gnm"] > impact["rgg"] > impact["rhg"]
    assert impact["rhg"] < 0.7


def test_all_solvers_produce_valid_solutions_all_modes():
    g = gen.rgg2d(600, avg_deg=8, seed=2)
    weights = {}
    for algo in ("greedy", "rg", "rnp"):
        for mode in ("sync", "async"):
            pg = part.partition_graph(g, 4, window_cap=12)
            members, _, _ = S.solve(
                pg, algo, D.DisReduConfig(heavy_k=6, mode=mode)
            )
            assert g.is_independent_set(members)
            weights[(algo, mode)] = g.set_weight(members)
    # reduce-and-peel dominates plain greedy (paper Table 7.1 ordering)
    assert weights[("rnp", "sync")] >= weights[("greedy", "sync")]
    assert weights[("rnp", "async")] >= weights[("greedy", "async")]


def test_solution_quality_vs_sequential_baseline():
    """Distributed RnPA vs the HtWIS-style sequential baseline (Table 7.1:
    distributed keeps ≥97% at large p; we assert a conservative 93%)."""
    rat = []
    for seed in range(3):
        g = gen.rgg2d(700, avg_deg=8, seed=seed)
        w_seq, _ = seq.solve_reduce_and_peel(g)
        pg = part.partition_graph(g, 8, window_cap=12)
        members, _, _ = S.solve(
            pg, "rnp", D.DisReduConfig(heavy_k=6, mode="async")
        )
        rat.append(g.set_weight(members) / max(w_seq, 1))
    assert np.mean(rat) > 0.93, rat


def test_offset_accounting_consistent():
    """Σ original weights over reconstructed members == reported kernel
    value + offsets when the kernel is solved exactly (small instance)."""
    g = gen.random_graph(12, 0.4, seed=9)
    best, _ = mwis_exact(g)
    pg = part.partition_graph(g, 2, window_cap=8, pad_to=SMALL_PAD)
    state, prob, _ = D.disredu(pg, D.DisReduConfig(heavy_k=6))
    wgt, indep = residual_exact_weight(g, pg, state, prob)
    assert indep and wgt == best


def test_kernel_compaction_driver():
    """Shape descent (reduce → measure kernel → restrict onto a smaller
    ladder cell → continue) stays sound and matches plain RnP bit for
    bit — compaction is an exact restriction, not a heuristic."""
    g = gen.rgg2d(1200, avg_deg=8, seed=4)
    cfg = D.DisReduConfig(mode="async", heavy_k=6)
    pg = part.partition_graph(g, 4, window_cap=12)
    m_plain, _, _ = S.solve(pg, "rnp", cfg)
    dcfg = D.DisReduConfig(mode="async", heavy_k=6, descent=True,
                           descent_every=2)
    m_comp, stats = S.solve_staged(g, 4, "rnp", dcfg, window_cap=12)
    assert g.is_independent_set(m_comp)
    assert stats["descents"] >= 1
    assert stats["kernel_ratio"] < 1.0
    assert np.array_equal(m_comp, m_plain)
