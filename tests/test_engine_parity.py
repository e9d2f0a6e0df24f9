"""Parity harness: the aggregate-engine path == the seed path, bit for bit.

The engine refactor deleted the seed's per-rule aggregate recomputation
branches from ``rules.py``; this harness proves nothing changed by running
the full DisRedu{S,A} pipeline against the frozen seed implementation
(``tests/seed_oracle.py``) on the generator-graph matrix and asserting the
final ``status`` / ``w`` / ``offset`` arrays are **bit-identical**:

  * engine schedule "cheap"       == seed per-rule path (fused_sweeps=False),
  * engine schedule "cheap-fused" == seed fused path   (fused_sweeps=True),
  * all aggregate backends (jnp / blocked / pallas-interpret) agree exactly
    (int32 payloads — addition is associative, so layout cannot matter),
  * the engine-computed window bits (``ctx.act_bits`` / ``ctx.clique`` —
    fused edge-pass OR payloads on the blocked backends, the vectorized
    [V, D] form on jnp) == the seed's D-unrolled window gather loop, for
    arbitrary status/weight states,
  * the solver paths (greedy / RnP) are unchanged by the backend routing,
    and distributed greedy still equals the ``sequential.solve_greedy``
    priority-greedy oracle exactly.

The shard_map-path parity (same assertion across the production execution
path) lives in ``tests/test_shardmap.py`` (multi-device subprocess).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as D
from repro.core import engine as E
from repro.core import partition as part
from repro.core import rules as R
from repro.core import sequential as seq
from repro.core import solvers as S
from repro.graphs import generators as gen
from tests import seed_oracle as O
from tests.helpers import SMALL_PAD


def _small_graphs():
    """Brute-force-scale graphs sharing one compiled program (SMALL_PAD)."""
    out = []
    for seed in range(4):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(4, 13))
        out.append((f"rand{seed}", gen.random_graph(n, 0.4, seed=seed)))
    return out


def _generator_graphs():
    """One instance per paper generator family (laptop scale)."""
    return [
        ("rgg", gen.rgg2d(240, avg_deg=7, seed=1)),
        ("rhg", gen.rhg_like(240, avg_deg=6, seed=2)),
        ("gnm", gen.gnm(200, 600, seed=3)),
    ]


def _assert_bit_identical(state_engine, state_seed, label):
    np.testing.assert_array_equal(
        np.asarray(state_engine.status), np.asarray(state_seed.status),
        err_msg=f"{label}: status diverged",
    )
    np.testing.assert_array_equal(
        np.asarray(state_engine.w), np.asarray(state_seed.w),
        err_msg=f"{label}: weights diverged",
    )
    assert int(state_engine.offset) == int(state_seed.offset), \
        f"{label}: offset diverged"


def _run_matrix(schedule, fused, graphs, pad=None, ps=(1, 2)):
    for name, g in graphs:
        for p in ps:
            for mode in ("sync", "async"):
                pg = part.partition_graph(
                    g, p, window_cap=8, common_cap=4, pad_to=pad
                )
                se, _, _ = D.disredu(pg, D.DisReduConfig(
                    heavy_k=6, mode=mode, schedule=schedule
                ))
                so, _ = O.disredu_union_oracle(
                    pg, heavy_k=6, mode=mode, fused=fused
                )
                _assert_bit_identical(
                    se, so, f"{name}/p{p}/{mode}/{schedule}"
                )


def test_engine_cheap_matches_seed_per_rule_path_small():
    _run_matrix("cheap", False, _small_graphs(), pad=SMALL_PAD)


def test_engine_cheap_fused_matches_seed_fused_path_small():
    _run_matrix("cheap-fused", True, _small_graphs(), pad=SMALL_PAD)


@pytest.mark.slow
@pytest.mark.parametrize("schedule,fused", [
    ("cheap", False), ("cheap-fused", True),
])
def test_engine_matches_seed_on_generator_matrix(schedule, fused):
    _run_matrix(schedule, fused, _generator_graphs())


@pytest.mark.parametrize("backend", ["blocked", "pallas"])
def test_backends_bit_identical_to_jnp(backend):
    """Blocked-ELL backends (ref + pallas interpret) == jnp, bit for bit."""
    for name, g in _small_graphs():
        pg = part.partition_graph(
            g, 2, window_cap=8, common_cap=4, pad_to=SMALL_PAD
        )
        for schedule in ("cheap", "cheap-fused"):
            sj, _, _ = D.disredu(pg, D.DisReduConfig(
                heavy_k=6, schedule=schedule, backend="jnp"
            ))
            sb, _, _ = D.disredu(pg, D.DisReduConfig(
                heavy_k=6, schedule=schedule, backend=backend
            ))
            _assert_bit_identical(sb, sj, f"{name}/{schedule}/{backend}")


@pytest.mark.slow
def test_blocked_backend_bit_identical_on_generator_graph():
    g = gen.rgg2d(240, avg_deg=7, seed=4)
    pg = part.partition_graph(g, 4, window_cap=8)
    sj, _, _ = D.disredu(pg, D.DisReduConfig(
        heavy_k=6, mode="async", schedule="cheap-fused", backend="jnp"
    ))
    sb, _, _ = D.disredu(pg, D.DisReduConfig(
        heavy_k=6, mode="async", schedule="cheap-fused", backend="blocked"
    ))
    _assert_bit_identical(sb, sj, "rgg/p4/async/blocked")


# --------------------------------------------------------------------- #
# window-bit parity: engine ctx == the frozen D-unrolled seed loop
# --------------------------------------------------------------------- #
def _assert_window_bits_match_seed(pg, label, n_states=4):
    """For arbitrary status/weight states, every backend's act_bits/clique
    must equal the seed loop bit for bit."""
    req = frozenset({"act_bits", "clique", "S", "deg", "M", "only"})
    rng = np.random.default_rng(0)
    probs = {b: D.build_union_problem(pg, b) for b in E.BACKENDS}
    for k in range(n_states):
        state = R.init_state(
            probs["jnp"].w0, probs["jnp"].is_local, probs["jnp"].is_ghost
        )
        if k:  # perturb: arbitrary statuses + shrunk weights
            st = rng.integers(0, 4, size=probs["jnp"].w0.shape[0])
            state = state._replace(
                status=jnp.asarray(st.astype(np.int8)),
                w=jnp.asarray(
                    rng.integers(0, 50, size=st.shape).astype(np.int32)
                ),
            )
        want_bits = np.asarray(O._window_active_bits(state, probs["jnp"].aux))
        want_clq = np.asarray(
            O._is_clique(state, probs["jnp"].aux, jnp.asarray(want_bits))
        )
        for backend, prob in probs.items():
            ctx = E.compute_ctx(
                state, prob.aux, req, backend=backend, plan=prob.plan
            )
            np.testing.assert_array_equal(
                np.asarray(ctx.act_bits), want_bits,
                err_msg=f"{label}/{backend}/state{k}: act_bits diverged",
            )
            np.testing.assert_array_equal(
                np.asarray(ctx.clique), want_clq,
                err_msg=f"{label}/{backend}/state{k}: clique diverged",
            )


def test_window_bits_match_seed_loop_small():
    for name, g in _small_graphs():
        for p in (1, 2):
            pg = part.partition_graph(
                g, p, window_cap=8, common_cap=4, pad_to=SMALL_PAD
            )
            _assert_window_bits_match_seed(pg, f"{name}/p{p}")


@pytest.mark.slow
def test_window_bits_match_seed_loop_on_generator_matrix():
    for name, g in _generator_graphs():
        pg = part.partition_graph(g, 4, window_cap=12)
        _assert_window_bits_match_seed(pg, f"{name}/p4", n_states=2)


# --------------------------------------------------------------------- #
# solver-path parity: backend routing must not change solver results
# --------------------------------------------------------------------- #
def test_solver_paths_identical_across_backends_and_greedy_oracle():
    for name, g in (
        [("rgg300", gen.rgg2d(300, avg_deg=7, seed=5))]
        + [gr for gr in _small_graphs()[:2]]
    ):
        for algo in ("greedy", "rg", "rnp"):
            members = {}
            for backend in E.BACKENDS:
                pg = part.partition_graph(g, 2, window_cap=8, common_cap=4)
                m, _, _ = S.solve(pg, algo, D.DisReduConfig(
                    heavy_k=6, mode="async", backend=backend
                ))
                assert g.is_independent_set(m), f"{name}/{algo}/{backend}"
                members[backend] = m
            for backend in ("blocked", "pallas"):
                np.testing.assert_array_equal(
                    members[backend], members["jnp"],
                    err_msg=f"{name}/{algo}/{backend}: members diverged",
                )
            if algo == "greedy":
                _, m_seq = seq.solve_greedy(g)
                np.testing.assert_array_equal(
                    members["jnp"], m_seq,
                    err_msg=f"{name}: distributed greedy != sequential "
                            "priority greedy",
                )


@pytest.mark.parametrize("backend", ["blocked", "pallas"])
def test_plan_packs_one_padding_edge_per_nil_row(backend):
    """The partition pads every PE's edge list with (nil, nil) edges; the
    plan packs one per nil row, so its edge budget follows the real edges,
    and every row — nil rows included — reduces exactly as under jnp."""
    from repro.kernels.segment_coo.ops import pack_blocks

    g = gen.gnm(300, 1200, seed=7)
    pg = part.partition_graph(g, 4, window_cap=8, pad_to=dict(E=2000))
    prob = D.build_union_problem(pg, backend, r_blk=8)
    row = np.asarray(prob.aux.row)
    col = np.asarray(prob.aux.col)
    real = np.asarray(prob.aux.gid)[row] >= 0
    n = prob.w0.shape[0]

    def e_blk(edges=None):
        return pack_blocks(row, n, r_blk=8, e_blk_multiple=E.E_BLK_MULTIPLE,
                           edges=edges)[2]

    got = prob.plan.edge_perm.shape[1]
    assert got < e_blk()
    assert got <= e_blk(np.flatnonzero(real)) + E.E_BLK_MULTIPLE

    # payloads as the engine builds them: functions of the endpoints, sums
    # masked by edge activity (a nil vertex is never active)
    rng = np.random.default_rng(3)
    val = jnp.asarray(rng.integers(-1000, 1000, size=n).astype(np.int32))
    active = jnp.asarray(np.asarray(prob.is_local) | np.asarray(prob.is_ghost))
    eact = active[row] & active[col]
    kw = dict(
        data_sum=jnp.stack([jnp.where(eact, val[col], 0),
                            eact.astype(jnp.int32)], axis=1),
        data_max=jnp.stack([val[col], jnp.where(eact, col, -1)], axis=1),
        data_min=val[row] - val[col],
        data_or=jnp.where(active[col], val[col] & 0xFF, 0),
        or_nbits=8,
    )
    want = E.aggregate(jnp.asarray(row), n, backend="jnp", **kw)
    have = E.aggregate(jnp.asarray(row), n, backend=backend, plan=prob.plan,
                       **kw)
    for h, w in zip(have, want):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(w))


def test_row_arrays_sorted_for_aggregate_sorted_flag():
    """engine.aggregate passes indices_are_sorted=True for Aux rows — the
    partition (and its union concatenation) must keep rows sorted."""
    for name, g in _small_graphs()[:2] + [("rgg", gen.rgg2d(200, avg_deg=6,
                                                            seed=6))]:
        for p in (1, 3):
            pg = part.partition_graph(g, p, window_cap=8)
            for i in range(p):
                assert (np.diff(pg.row[i]) >= 0).all(), f"{name}/pe{i}"
            prob = D.build_union_problem(pg)
            assert (np.diff(np.asarray(prob.aux.row)) >= 0).all(), \
                f"{name}/union"
