"""Shape descent (adaptive kernel compaction) — bit-identity and policy.

The staged driver's contract: for any graph, PE count, backend, and algo,
``solve_staged`` with descent ON returns the SAME member mask as with
descent OFF (which itself equals the monolithic ``solve``) — compaction is
an exact restriction of the partition and stage chunking visits the same
states as the monolithic while_loops.  These tests pin that contract on
seeded generator families and (when hypothesis is installed) random
GNM/RGG instances, plus the policy pieces around it: the int32 residual
weight gate, descent-tagged plan-cache counters, checkpoint/resume across
a descent boundary, and the serving integration (descent="auto" parity +
oversize admission through the descent entry cells).
"""

import numpy as np
import pytest

from repro.core import distributed as D
from repro.core import engine as E
from repro.core import partition as part
from repro.core import serve as SV
from repro.core import solvers as S
from repro.core import validate as VAL
from repro.graphs.generators import gnm, rgg2d

#: Tiny ladder so descents trigger on test-sized graphs.
TINY_LADDER = tuple(
    S.LadderCell(name=f"t{L}", L=L, E=E, G=max(L // 2, 4),
                 B=max(L // 4, 4), S=max(L // 4, 4))
    for L, E in ((8, 128), (16, 256), (32, 512), (64, 1024), (128, 2048))
)


def _cfgs(backend="jnp", mode="async"):
    base = dict(mode=mode, heavy_k=6, backend=backend)
    return (D.DisReduConfig(**base),
            D.DisReduConfig(**base, descent=True, descent_every=2))


# --------------------------------------------------------------------- #
# bit-identity: descent on == descent off == monolithic solve
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("algo", ["greedy", "rg", "rnp"])
@pytest.mark.parametrize("backend", ["jnp", "blocked"])
def test_descent_parity_across_backends_and_algos(algo, backend):
    g = rgg2d(500, avg_deg=8, seed=3)
    cfg0, cfgd = _cfgs(backend)
    pg = part.partition_graph(g, 4, window_cap=12)
    m_mono, _, _ = S.solve(pg, algo, cfg0)
    m_off, _ = S.solve_staged(g, 4, algo, cfg0, window_cap=12)
    m_on, st = S.solve_staged(g, 4, algo, cfgd, window_cap=12,
                              ladder=TINY_LADDER)
    assert np.array_equal(m_mono, m_off)
    assert np.array_equal(m_mono, m_on)
    assert st["descents"] >= 1, "tiny ladder should trigger a descent"
    assert g.is_independent_set(m_on)


@pytest.mark.parametrize("gen,kw", [
    (gnm, dict(m=1600)), (rgg2d, dict(avg_deg=8)),
])
def test_descent_parity_seeded_families(gen, kw):
    for seed in (0, 4):
        g = gen(400, seed=seed, **kw)
        cfg0, cfgd = _cfgs()
        m_off, _ = S.solve_staged(g, 2, "rnp", cfg0, window_cap=12)
        m_on, st = S.solve_staged(g, 2, "rnp", cfgd, window_cap=12,
                                  ladder=TINY_LADDER)
        assert np.array_equal(m_off, m_on), f"{gen.__name__} seed={seed}"


def test_descent_parity_sync_mode_and_multiple_descents():
    g = rgg2d(500, avg_deg=8, seed=7)
    cfg0, cfgd = _cfgs(mode="sync")
    m_off, _ = S.solve_staged(g, 2, "rnp", cfg0)
    m_on, st = S.solve_staged(g, 2, "rnp", cfgd, ladder=TINY_LADDER)
    assert np.array_equal(m_off, m_on)
    assert st["descents"] >= 2, st["path"]
    # the path walks strictly downward in L
    Ls = [e["L"] for e in st["path"]]
    assert all(a > b for a, b in zip(Ls, Ls[1:])), Ls


def test_descent_property_random_instances():
    hyp = pytest.importorskip("hypothesis")  # optional dep
    from hypothesis import given, settings, strategies as hst

    @settings(max_examples=10, deadline=None)
    @given(hst.integers(0, 10_000), hst.sampled_from([1, 2]),
           hst.sampled_from(["gnm", "rgg"]))
    def prop(seed, p, fam):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(20, 120))
        g = gnm(n, 3 * n, seed=seed) if fam == "gnm" \
            else rgg2d(n, avg_deg=6, seed=seed)
        cfg0, cfgd = _cfgs()
        m_off, _ = S.solve_staged(g, p, "rg", cfg0, window_cap=8)
        m_on, _ = S.solve_staged(g, p, "rg", cfgd, window_cap=8,
                                 ladder=TINY_LADDER)
        assert np.array_equal(m_off, m_on)

    prop()


# --------------------------------------------------------------------- #
# residual weight gate: int64 → int32 must be checked, never wrap
# --------------------------------------------------------------------- #


def test_residual_weights_near_int32_max():
    w = np.array([0, 1, VAL.I32_MAX], dtype=np.int64)
    out = VAL.residual_weights(w)
    assert out.dtype == np.int32 and int(out[2]) == VAL.I32_MAX

    with pytest.raises(VAL.InvalidInstance) as ei:
        VAL.residual_weights(np.array([VAL.I32_MAX + 1], dtype=np.int64))
    assert ei.value.reason == VAL.REASON_BAD_WEIGHT

    with pytest.raises(VAL.InvalidInstance):
        VAL.residual_weights(np.array([-1], dtype=np.int64))


def test_compact_partition_rejects_overflowing_residual():
    """The old solve_compact silently wrapped int64 folded weights via
    .astype(np.int32); compact_partition must reject them instead."""
    g = gnm(24, 60, seed=1)
    pg = part.partition_graph(g, 2, window_cap=8)
    status = np.zeros(pg.p * pg.V, dtype=np.int8)  # everything alive
    w = np.zeros(pg.p * pg.V, dtype=np.int64)
    w[: pg.V] = VAL.I32_MAX  # at the limit: fine
    pg2 = part.compact_partition(pg, status, w)
    assert int(np.asarray(pg2.w0).max()) == VAL.I32_MAX

    w[0] = VAL.I32_MAX + 1  # one past: must raise, not wrap negative
    alive0 = bool(pg.is_local[0, 0] or pg.is_ghost[0, 0])
    assert alive0  # slot 0 is a real vertex in this layout
    with pytest.raises(VAL.InvalidInstance) as ei:
        part.compact_partition(pg, status, w)
    assert ei.value.reason == VAL.REASON_BAD_WEIGHT


# --------------------------------------------------------------------- #
# descent-tagged plan-cache counters
# --------------------------------------------------------------------- #


def test_plan_cache_descent_counters():
    cache = E.PlanCache(max_entries=8)
    builds = []
    cache.get_or_build("k1", lambda: builds.append(1) or "p1",
                       tag="descent")
    cache.get_or_build("k1", lambda: builds.append(1) or "p1",
                       tag="descent")
    cache.get_or_build("k2", lambda: builds.append(1) or "p2")
    s = cache.stats
    assert (s.descent_hits, s.descent_misses) == (1, 1)
    # descent counters are a tagged subset of the plain totals
    assert s.misses == 2 and s.hits == 1
    assert len(builds) == 2


def test_descent_plans_hit_cache_on_repeat_solve():
    g = rgg2d(400, avg_deg=8, seed=5)
    cfg = D.DisReduConfig(mode="async", heavy_k=6, backend="blocked",
                          descent=True, descent_every=2)
    cache = E.PlanCache(max_entries=32)
    m1, st1 = S.solve_staged(g, 2, "rnp", cfg, window_cap=12,
                             ladder=TINY_LADDER, plan_cache=cache)
    assert st1["descents"] >= 1
    miss1 = cache.stats.descent_misses
    m2, _ = S.solve_staged(g, 2, "rnp", cfg, window_cap=12,
                           ladder=TINY_LADDER, plan_cache=cache)
    assert np.array_equal(m1, m2)
    s = cache.stats
    assert s.descent_misses == miss1, "repeat solve rebuilt descent plans"
    assert s.descent_hits >= st1["descents"]


# --------------------------------------------------------------------- #
# checkpoint + resume across a descent boundary
# --------------------------------------------------------------------- #


def test_resume_across_descent_boundary(tmp_path):
    from repro.distributed.checkpoint import CheckpointManager

    g = rgg2d(400, avg_deg=8, seed=9)
    cfg = D.DisReduConfig(mode="async", heavy_k=6, descent=True,
                          descent_every=2)
    m_ref, st_ref = S.solve_staged(g, 2, "rnp", cfg, window_cap=12,
                                   ladder=TINY_LADDER)
    assert st_ref["descents"] >= 1

    class Die(RuntimeError):
        pass

    ck = CheckpointManager(str(tmp_path / "ck"), async_write=False)

    def killer(descents, cell_name):
        raise Die(f"killed after descent {descents} -> {cell_name}")

    with pytest.raises(Die):
        S.solve_staged(g, 2, "rnp", cfg, window_cap=12,
                       ladder=TINY_LADDER, ckpt=ck, on_descent=killer)
    assert ck.latest_step() == 1  # saved before the fault fired

    m_res, st_res = S.solve_staged(g, 2, "rnp", cfg, window_cap=12,
                                   ladder=TINY_LADDER, ckpt=ck,
                                   resume=True)
    assert np.array_equal(m_ref, m_res)
    assert st_res["descents"] == st_ref["descents"]
    assert [e["cell"] for e in st_res["path"]] == \
        [e["cell"] for e in st_ref["path"]]


# --------------------------------------------------------------------- #
# serving integration
# --------------------------------------------------------------------- #


def test_serve_descent_auto_matches_off():
    gs = [gnm(200, 700, seed=s) for s in range(3)]  # serve_s bucket
    off = SV.MWISService(SV.ServeConfig(algo="rg", verify="full"))
    on = SV.MWISService(SV.ServeConfig(algo="rg", verify="full",
                                       descent="auto", descent_min_L=256))
    r_off = off.solve_batch(gs)
    r_on = on.solve_batch(gs)
    for a, b in zip(r_off, r_on):
        assert a.ok and b.ok
        assert np.array_equal(a.members, b.members)
        assert a.weight == b.weight
    assert on.stats["descent_solves"] == len(gs)


def test_serve_oversize_admitted_through_descent_cells():
    big = SV.serve_cells()[-1].L + 200
    g = gnm(big, 2 * big, seed=2)
    off = SV.MWISService(SV.ServeConfig(algo="rg"))
    r = off.solve_one(g)
    assert not r.ok and r.reason == VAL.REASON_OVERSIZE

    on = SV.MWISService(SV.ServeConfig(algo="rg", descent="auto"))
    r = on.solve_one(g)
    assert r.ok, (r.reason, r.error)
    assert VAL.verify_result(g, r.members, r.weight).ok
    st = on.stats
    assert st["oversize_admitted"] == 1 and st["descent_solves"] == 1


def test_serve_descent_rejects_beyond_descent_cells():
    huge_n = max(c.L for c in SV.descent_entry_cells()) + 1
    g = SV.Graph(indptr=np.zeros(huge_n + 1, np.int64),
                 indices=np.zeros(0, np.int32),
                 weights=np.ones(huge_n, np.int32))
    svc = SV.MWISService(SV.ServeConfig(descent="auto"))
    r = svc.solve_one(g)
    assert not r.ok and r.reason == VAL.REASON_OVERSIZE
