"""Batched MWIS serving layer: cache semantics, vmap invariance, bucketing,
CLI validation, and the bench-regression gate (benchmarks/compare.py)."""

import copy
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import engine as E
from repro.core import serve as SV
from repro.core import solvers as SOL
from repro.core.distributed import DisReduConfig
from repro.core.partition import partition_graph
from repro.graphs.generators import gnm

# --------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------- #


def _reweighted(g, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 201, size=g.n).astype(np.int32)
    return type(g)(indptr=g.indptr, indices=g.indices, weights=w)


def _oracle(g, algo, backend):
    """The unbatched single-instance path on the same cell shapes."""
    cell = SV.bucket_for(g.n, g.num_directed_edges)
    pg = partition_graph(
        g, 1, window_cap=cell.D, common_cap=cell.Dc,
        pad_to=dict(L=cell.L, G=cell.G, E=cell.E, B=cell.B, S=cell.S),
    )
    cfg = DisReduConfig(
        backend=backend, r_blk=None if backend == "jnp" else cell.r_blk
    )
    members, _, _ = SOL.solve(pg, algo, cfg)
    return members


# --------------------------------------------------------------------- #
# PlanCache semantics
# --------------------------------------------------------------------- #


def test_plan_cache_lru_eviction_bound():
    c = E.PlanCache(max_entries=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1       # refreshes recency: b is now oldest
    c.put("c", 3)                # evicts b
    assert len(c) == 2
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    s = c.stats
    assert s.evictions == 1 and s.size == 2


def test_plan_cache_raising_build_does_not_poison():
    """A build() that raises leaves NO entry behind: the miss is counted
    once, the error is counted, and a later successful build repopulates."""
    c = E.PlanCache(max_entries=4)
    calls = [0]

    def bad():
        calls[0] += 1
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        c.get_or_build("k", bad)
    s = c.stats
    assert len(c) == 0            # nothing cached for "k"
    assert s.misses == 1 and s.errors == 1 and s.hits == 0

    assert c.get_or_build("k", lambda: 42) == 42   # retry rebuilds
    assert c.get_or_build("k", bad) == 42          # now a hit; bad not called
    assert calls[0] == 1
    s = c.stats
    assert s.errors == 1 and s.hits == 1 and s.misses == 2


def test_topology_hash_semantics():
    g = gnm(30, 60, seed=0)
    row, col = g.edge_sources(), g.indices
    h0 = E.topology_hash(row, col, g.n)
    # permutation of the same edge multiset -> same hash
    perm = np.random.default_rng(0).permutation(row.shape[0])
    assert E.topology_hash(row[perm], col[perm], g.n) == h0
    # removing an edge (both directions) -> different hash
    keep = ~(((row == row[0]) & (col == col[0]))
             | ((row == col[0]) & (col == row[0])))
    assert E.topology_hash(row[keep], col[keep], g.n) != h0
    # different vertex budget -> different hash
    assert E.topology_hash(row, col, g.n + 1) != h0


def test_service_cache_hit_miss_semantics():
    svc = SV.MWISService(SV.ServeConfig(algo="rg", backend="jnp"))
    g = gnm(24, 50, seed=1)
    svc.solve_one(g)
    assert svc.stats["cache_misses"] == 1
    # identical topology -> hit
    svc.solve_one(g)
    assert svc.stats["cache_hits"] == 1
    # weights-only change -> still a hit (topology key excludes weights)
    svc.solve_one(_reweighted(g, 7))
    assert svc.stats["cache_hits"] == 2
    assert svc.stats["cache_misses"] == 1
    # edge change -> miss
    svc.solve_one(gnm(24, 51, seed=1))
    assert svc.stats["cache_misses"] == 2


def test_service_cache_eviction_bound():
    svc = SV.MWISService(
        SV.ServeConfig(algo="rg", backend="jnp", cache_entries=2)
    )
    for s in range(4):
        svc.solve_one(gnm(20, 40, seed=s))
    st = svc.stats
    assert st["cache_size"] <= 2
    assert st["cache_evictions"] == 2


def test_cached_topology_reuse_is_bit_identical():
    svc = SV.MWISService(SV.ServeConfig(algo="rg", backend="jnp"))
    g = gnm(26, 55, seed=3)
    first = svc.solve_one(g)
    again = svc.solve_one(g)          # served from cache
    assert np.array_equal(first.members, again.members)
    assert first.weight == again.weight


# --------------------------------------------------------------------- #
# vmap invariance: batched == sequence of single-instance runs, per backend
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("backend", ["jnp", "blocked", "pallas"])
@pytest.mark.parametrize("algo", ["greedy", "rg"])
def test_batched_matches_single_instance(backend, algo):
    k = 2 if backend == "pallas" else 4
    graphs = [gnm(18 + 3 * i, 40 + 4 * i, seed=i) for i in range(k)]
    svc = SV.MWISService(SV.ServeConfig(algo=algo, backend=backend))
    res = svc.solve_batch(graphs)
    for g, r in zip(graphs, res):
        ref = _oracle(g, algo, backend)
        assert np.array_equal(r.members, ref), (backend, algo, g.n)


def test_batched_rnp_matches_single_instance():
    graphs = [gnm(20 + 2 * i, 45, seed=10 + i) for i in range(3)]
    svc = SV.MWISService(SV.ServeConfig(algo="rnp", backend="jnp"))
    for g, r in zip(graphs, svc.solve_batch(graphs)):
        assert np.array_equal(r.members, _oracle(g, "rnp", "jnp"))


def test_results_are_independent_sets_with_reported_weight():
    graphs = [gnm(30, 70, seed=20 + i) for i in range(5)]
    svc = SV.MWISService(SV.ServeConfig(algo="rg", backend="jnp"))
    for g, r in zip(graphs, svc.solve_batch(graphs)):
        src = g.edge_sources()
        assert not np.any(r.members[src] & r.members[g.indices])
        assert r.weight == int(g.weights[r.members].sum())
        assert r.members.shape == (g.n,)


def test_mixed_cell_batch_and_padding():
    # instances landing in different cells within one solve_batch call,
    # with a group size that is not a static batch bucket (padding path)
    graphs = [gnm(20, 40, seed=30), gnm(22, 44, seed=31),
              gnm(24, 48, seed=32), gnm(120, 300, seed=33)]
    svc = SV.MWISService(SV.ServeConfig(algo="rg", backend="jnp"))
    res = svc.solve_batch(graphs)
    assert [r.members.shape[0] for r in res] == [g.n for g in graphs]
    for g, r in zip(graphs, res):
        assert np.array_equal(r.members, _oracle(g, "rg", "jnp"))


# --------------------------------------------------------------------- #
# bucketing
# --------------------------------------------------------------------- #


def test_bucket_for_picks_smallest_admitting_cell():
    cells = SV.serve_cells()
    assert len(cells) >= 3
    assert SV.bucket_for(10, 20).name == cells[0].name
    # vertex count forces the next cell up even with few edges
    nxt = SV.bucket_for(cells[0].L + 1, 8)
    assert nxt.name == cells[1].name
    # edge count alone forces promotion too
    assert SV.bucket_for(8, cells[0].E + 2).name == cells[1].name


def test_bucket_for_rejects_oversized_instance():
    big = SV.serve_cells()[-1]
    with pytest.raises(ValueError, match="exceeds every serve cell"):
        SV.bucket_for(big.L + 1, 4)


@pytest.mark.parametrize("backend", ["jnp", "blocked"])
def test_aggregate_batched_matches_per_instance(backend):
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    n_rows, n_edges, B = 16, 48, 3
    seg = np.sort(rng.integers(0, n_rows, size=n_edges)).astype(np.int32)
    data = rng.integers(0, 1000, size=(B, n_edges)).astype(np.int32)
    plan = None
    if backend == "blocked":
        base_plan = E.build_plan(seg, n_rows, r_blk=8)
        plan = E.stack_plans([base_plan] * B)
    seg_b = jnp.asarray(np.broadcast_to(seg, (B, n_edges)).copy())
    s, m, *_ = E.aggregate_batched(
        seg_b, n_rows,
        data_sum=jnp.asarray(data), data_max=jnp.asarray(data),
        backend=backend, plan=plan,
    )
    for i in range(B):
        si, mi, *_ = E.aggregate(
            jnp.asarray(seg), n_rows, data_sum=jnp.asarray(data[i]),
            data_max=jnp.asarray(data[i]), backend=backend,
            plan=None if plan is None else base_plan,
        )
        assert np.array_equal(np.asarray(s[i]), np.asarray(si))
        assert np.array_equal(np.asarray(m[i]), np.asarray(mi))


def test_plan_stacking_bit_identity():
    # pad_plan slots follow the pack_blocks convention -> identical result
    g = gnm(40, 100, seed=5)
    pg = partition_graph(g, 1, window_cap=8, common_cap=4)
    row = np.asarray(pg.row[0])
    plan = E.build_plan(row, pg.V, r_blk=8)
    import jax.numpy as jnp
    data = np.random.default_rng(0).integers(0, 100, row.shape[0])
    data = jnp.asarray(data, jnp.int32)
    s0, _, _, _ = E.aggregate(jnp.asarray(row), pg.V, data_sum=data,
                              backend="blocked", plan=plan)
    padded = E.pad_plan(plan, plan.edge_perm.shape[1] + 24)
    s1, _, _, _ = E.aggregate(jnp.asarray(row), pg.V, data_sum=data,
                              backend="blocked", plan=padded)
    assert np.array_equal(np.asarray(s0), np.asarray(s1))


# --------------------------------------------------------------------- #
# CLI validation
# --------------------------------------------------------------------- #


def test_serve_cli_rejects_unknown_arch(capsys):
    from repro.launch import serve as L

    with pytest.raises(SystemExit) as e:
        L.main(["--arch", "nope"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    for arch in L.ARCHES:
        assert arch in err  # the error lists every valid choice


def test_serve_cli_exits_nonzero_on_demoted_backend(monkeypatch, capsys):
    # a run demoted down the fallback chain is not a run of the backend
    # asked for: the launcher must fail, not just print the demotion
    from repro.launch import serve as L

    real = SV.MWISService._execute_chunk

    def execute(self, cell, topos, backend):
        if backend == "blocked":
            raise RuntimeError("injected blocked-backend failure")
        return real(self, cell, topos, backend)

    monkeypatch.setattr(SV.MWISService, "_execute_chunk", execute)
    with pytest.raises(SystemExit) as e:
        L.main(["--arch", "mwis", "--backend", "blocked", "--requests", "4",
                "--batch", "4"])
    assert e.value.code == 1
    err = capsys.readouterr().err
    assert "blocked -> jnp" in err and "fallbacks" in err


# --------------------------------------------------------------------- #
# bench-regression gate (benchmarks/compare.py)
# --------------------------------------------------------------------- #

BASE = dict(
    meta={},
    results=[dict(
        graph="g1", n=100, m=200, p=2, schedule="cheap-fused",
        per_sweep_us={"jnp": 100.0, "blocked-auto": 200.0,
                      "pallas-interpret": 5000.0, "seed-fused-jnp": 110.0},
        greedy_round_us={"jnp": 50.0, "blocked-auto": 90.0},
        rnp_round_us={"jnp": 70.0},
    )],
)


def _run_compare(tmp_path, baseline, fresh, argv_extra=()):
    from benchmarks import compare as C

    b = tmp_path / "base.json"
    f = tmp_path / "fresh.json"
    out = tmp_path / "diff.json"
    b.write_text(json.dumps(baseline))
    f.write_text(json.dumps(fresh))
    rc = C.main([str(b), str(f), "--out", str(out), *argv_extra])
    return rc, json.loads(out.read_text())


def test_compare_clean_run_passes(tmp_path):
    rc, diff = _run_compare(tmp_path, BASE, copy.deepcopy(BASE))
    assert rc == 0
    assert diff["regressions"] == []
    assert any(c["gated"] for c in diff["cells"])


def test_compare_synthetic_2x_slowdown_fails(tmp_path):
    slow = copy.deepcopy(BASE)
    slow["results"][0]["per_sweep_us"]["jnp"] *= 2.0
    rc, diff = _run_compare(tmp_path, BASE, slow)
    assert rc == 1
    assert len(diff["regressions"]) == 1
    r = diff["regressions"][0]
    assert r["label"] == "jnp" and r["normalized"]


def test_compare_solver_round_regression_fails(tmp_path):
    slow = copy.deepcopy(BASE)
    slow["results"][0]["greedy_round_us"]["blocked-auto"] *= 3.0
    rc, diff = _run_compare(tmp_path, BASE, slow)
    assert rc == 1
    assert diff["regressions"][0]["metric"] == "greedy_round_us"


def test_compare_pallas_regression_warns_only(tmp_path):
    slow = copy.deepcopy(BASE)
    slow["results"][0]["per_sweep_us"]["pallas-interpret"] *= 10.0
    rc, diff = _run_compare(tmp_path, BASE, slow)
    assert rc == 0
    assert diff["regressions"] == []
    assert len(diff["warnings"]) == 1
    assert diff["warnings"][0]["label"] == "pallas-interpret"


def test_compare_normalization_cancels_machine_speed(tmp_path):
    # a uniformly 3x-slower machine (every metric AND the seed reference
    # scaled together) must NOT trip the gate
    slow = copy.deepcopy(BASE)
    row = slow["results"][0]
    for metric in ("per_sweep_us", "greedy_round_us", "rnp_round_us"):
        row[metric] = {k: v * 3.0 for k, v in row[metric].items()}
    rc, diff = _run_compare(tmp_path, BASE, slow)
    assert rc == 0
    assert diff["regressions"] == [] and diff["warnings"] == []


def test_compare_threshold_is_configurable(tmp_path):
    slow = copy.deepcopy(BASE)
    slow["results"][0]["per_sweep_us"]["jnp"] *= 1.3
    rc, _ = _run_compare(tmp_path, BASE, slow)
    assert rc == 0                    # 1.3x under default 1.5
    rc, _ = _run_compare(tmp_path, BASE, slow,
                         argv_extra=("--threshold", "1.2"))
    assert rc == 1


def test_compare_missing_rows_warn_not_fail(tmp_path):
    fresh = copy.deepcopy(BASE)
    fresh["results"] = []             # CI small mode ran a subset
    rc, diff = _run_compare(tmp_path, BASE, fresh)
    assert rc == 0
    assert diff["missing"]


SERVE_BASE = dict(
    meta={},
    results=[dict(cell="serve_xs", backend="jnp", batch=4,
                  instances_per_sec=50.0)],
    multidevice=[dict(cell="serve_xs", backend="jnp", batch=4, devices=4,
                      instances_per_sec=80.0, overlap_ratio=0.2)],
)


def test_compare_serve_slowdown_warns_but_never_gates(tmp_path):
    from benchmarks import compare as C

    slow = copy.deepcopy(SERVE_BASE)
    slow["results"][0]["instances_per_sec"] = 10.0    # 5x slower
    slow["multidevice"][0]["instances_per_sec"] = 10.0
    sb = tmp_path / "sbase.json"
    sf = tmp_path / "sfresh.json"
    sb.write_text(json.dumps(SERVE_BASE))
    sf.write_text(json.dumps(slow))
    b = tmp_path / "base.json"
    f = tmp_path / "fresh.json"
    out = tmp_path / "diff.json"
    b.write_text(json.dumps(BASE))
    f.write_text(json.dumps(BASE))
    rc = C.main([str(b), str(f), "--out", str(out),
                 "--serve-baseline", str(sb), "--serve-fresh", str(sf)])
    assert rc == 0                      # serve section never gates
    diff = json.loads(out.read_text())
    assert len(diff["serve"]["warnings"]) == 2
    assert all(not r["gated"] for r in diff["serve"]["rows"])
    # committed baselines without a devices column compare as devices=1
    assert {r["devices"] for r in diff["serve"]["rows"]} == {1, 4}


def test_compare_serve_missing_and_new_rows_warn_only(tmp_path):
    from benchmarks import compare as C

    fresh = dict(meta={}, results=[], multidevice=[
        dict(cell="serve_s", backend="jnp", batch=16, devices=4,
             instances_per_sec=5.0, overlap_ratio=0.1)])
    diff = C.compare_serve(SERVE_BASE, fresh, threshold=1.5)
    assert diff["warnings"] == []
    assert len(diff["missing"]) == 2    # both baseline rows absent
    new = [r for r in diff["rows"] if r["baseline_ips"] is None]
    assert len(new) == 1 and new[0]["cell"] == "serve_s"


# --------------------------------------------------------------------- #
# multi-device batch sharding + overlapped host pipeline
# --------------------------------------------------------------------- #


def test_batch_size_rounds_to_device_multiple():
    svc = SV.MWISService(SV.ServeConfig(backend="jnp"))
    svc._ndev = 4                       # as if 4 devices were visible
    assert svc._batch_size(1) == 4      # bucket 1 rounds up to a shardable 4
    assert svc._batch_size(3) == 4
    assert svc._batch_size(5) == 16     # bucket 16 already a multiple
    cell = svc.cells[0]._replace(serve_devices=2)
    assert svc._cell_ndev(cell) == 2    # per-cell cap wins over the mesh
    assert svc._batch_size(1, cell) == 2
    svc._ndev = 1
    assert svc._batch_size(1) == 1      # single device: buckets unchanged
    assert svc._batch_size(5) == 16


def test_batch_size_respects_max_batch_fallthrough():
    svc = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=8))
    svc._ndev = 4
    # no static bucket fits in (7, 8] -> fall through, still device-aligned
    assert svc._batch_size(7) == 8
    assert svc._batch_size(7) % 4 == 0


def test_stack_plans_pads_to_batch_multiple():
    g = gnm(40, 100, seed=5)
    pg = partition_graph(g, 1, window_cap=8, common_cap=4)
    row = np.asarray(pg.row[0])
    plan = E.build_plan(row, pg.V, r_blk=8)
    stacked = E.stack_plans([plan] * 3, batch_multiple=4)
    assert stacked.edge_perm.shape[0] == 4    # 3 plans padded to 4
    # phantom slot repeats the last plan bit-for-bit
    assert np.array_equal(np.asarray(stacked.edge_perm[3]),
                          np.asarray(stacked.edge_perm[2]))
    same = E.stack_plans([plan] * 4, batch_multiple=4)
    assert same.edge_perm.shape[0] == 4       # already aligned: no padding
    with pytest.raises(ValueError, match="batch_multiple"):
        E.stack_plans([plan], batch_multiple=0)


def test_service_rejects_excess_devices():
    with pytest.raises(ValueError, match="exceeds the .* visible"):
        SV.MWISService(SV.ServeConfig(backend="jnp", devices=4096))


def test_serve_cli_rejects_excess_devices(capsys):
    from repro.launch import serve as L

    with pytest.raises(SystemExit) as e:
        L.main(["--arch", "mwis", "--devices", "4096"])
    assert e.value.code == 2
    assert "visible" in capsys.readouterr().err


def test_pipeline_parity_and_stage_stats():
    # multi-chunk call: pipeline on and off must be bit-identical, and the
    # per-stage timing telemetry must cover every chunk either way
    graphs = [gnm(18 + 2 * i, 40 + 3 * i, seed=50 + i) for i in range(6)]
    on = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=2,
                                       pipeline=True))
    off = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=2,
                                        pipeline=False))
    r_on = on.solve_batch(graphs)
    r_off = off.solve_batch(graphs)
    for a, b in zip(r_on, r_off):
        assert a.ok and b.ok
        assert a.weight == b.weight
        assert np.array_equal(a.members, b.members)
    s_on, s_off = on.stats, off.stats
    assert s_on["pipelined_chunks"] == s_on["chunks"] == 3
    assert s_off["pipelined_chunks"] == 0 and s_off["chunks"] == 3
    for s in (s_on, s_off):
        assert s["stage_ms"]["pack"] > 0 and s["stage_ms"]["solve"] > 0
        assert set(s["stage_p50_ms"]) == {"pack", "transfer", "solve",
                                          "fetch"}
        assert s["wall_ms"] > 0 and 0.0 <= s["overlap_ratio"] < 1.0


def test_pipeline_single_chunk_takes_sync_path():
    # one chunk has nothing to overlap with -> the sync path runs (this
    # also keeps the _execute_chunk monkeypatch seam on solve_one)
    svc = SV.MWISService(SV.ServeConfig(backend="jnp"))
    r = svc.solve_one(gnm(20, 40, seed=60))
    assert r.ok
    assert svc.stats["pipelined_chunks"] == 0 and svc.stats["chunks"] == 1


def test_pipeline_poisoned_batchmates_are_isolated():
    from repro.core import validate as VAL
    from repro.core.graph import Graph

    good = [gnm(20, 40, seed=70 + s) for s in range(5)]
    nan_g = Graph(indptr=np.array([0, 1, 2]),
                  indices=np.array([1, 0], np.int32),
                  weights=np.array([np.nan, 1.0]))
    svc = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=2,
                                        pipeline=True))
    res = svc.solve_batch([good[0], good[1], nan_g, good[2], good[3],
                           good[4]])
    assert not res[2].ok and res[2].reason == VAL.REASON_BAD_WEIGHT
    ref = SV.MWISService(SV.ServeConfig(backend="jnp")).solve_batch(good)
    for got, want in zip([res[0], res[1], res[3], res[4], res[5]], ref):
        assert got.ok and np.array_equal(got.members, want.members)


def test_pipeline_dispatch_failure_falls_back_to_sync_path(monkeypatch):
    # a launch that raises mid-pipeline must not lose the chunk: it is
    # retired through the synchronous fallback-chain path
    graphs = [gnm(18 + 2 * i, 40, seed=80 + i) for i in range(4)]
    svc = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=2,
                                        pipeline=True))
    ref = SV.MWISService(
        SV.ServeConfig(backend="jnp", max_batch=2, pipeline=False)
    ).solve_batch(graphs)
    boom = {"n": 0}
    real = SV.MWISService._launch_chunk

    def flaky(self, staged):
        boom["n"] += 1
        if boom["n"] == 1:
            raise RuntimeError("injected launch failure")
        return real(self, staged)

    monkeypatch.setattr(SV.MWISService, "_launch_chunk", flaky)
    res = svc.solve_batch(graphs)
    for got, want in zip(res, ref):
        assert got.ok and np.array_equal(got.members, want.members)
    assert svc.stats["pipeline_retries"] == 1


@pytest.mark.parametrize("seam", ["launch", "fetch", "fallback"])
def test_retry_after_donation_restages_weights(monkeypatch, seam):
    # the launch donates the stacked weight plane; a chunk that fails
    # after its launch (pipelined dispatch, in-flight fetch, or the sync
    # path's backend fallback) must re-stage from the host weights and
    # never re-run on the donated device array
    graphs = [gnm(18 + 2 * i, 40, seed=80 + i) for i in range(4)]
    if seam == "fallback":
        graphs = graphs[:2]     # one chunk -> the synchronous path
    backend = "blocked" if seam == "fallback" else "jnp"
    ref = SV.MWISService(
        SV.ServeConfig(backend="jnp", max_batch=2, pipeline=False)
    ).solve_batch(graphs)
    svc = SV.MWISService(SV.ServeConfig(backend=backend, max_batch=2,
                                        pipeline=True))
    donated = []
    real_launch = SV.MWISService._launch_chunk
    real_fetch = SV.MWISService._fetch_chunk

    def launch(self, staged):
        inflight = real_launch(self, staged)
        inflight.members.block_until_ready()
        donated.append(staged.args[0])
        if seam != "fetch" and len(donated) == 1:
            raise RuntimeError("injected failure after launch")
        return inflight

    def fetch(self, inflight):
        if seam == "fetch" and len(donated) == 2 and not fetch.failed:
            fetch.failed = True
            raise RuntimeError("injected failure after launch")
        return real_fetch(self, inflight)

    fetch.failed = False
    monkeypatch.setattr(SV.MWISService, "_launch_chunk", launch)
    monkeypatch.setattr(SV.MWISService, "_fetch_chunk", fetch)
    res = svc.solve_batch(graphs)
    assert donated and all(w.is_deleted() for w in donated)
    for got, want in zip(res, ref):
        assert got.ok and np.array_equal(got.members, want.members)
    st = svc.stats
    assert st["solve_errors"] == 0
    if seam == "fallback":
        assert st["fallbacks"] == 1 and st["backend_active"] == "jnp"
    else:
        assert st["pipeline_retries"] == 1 and st["fallbacks"] == 0


def test_descent_auto_takes_staged_single_device_path():
    # descent-routed instances bypass the sharded/pipelined chunk machinery
    # entirely (per-instance staged path) and still solve correctly
    cells = SV.serve_cells()
    big = cells[-1]
    n = big.L // 2 + 8
    g = gnm(n, 2 * n, seed=90)
    svc = SV.MWISService(SV.ServeConfig(
        backend="jnp", descent="auto", descent_min_L=big.L))
    r = svc.solve_batch([g])[0]
    assert r.ok
    assert svc.stats["descent_solves"] == 1
    assert svc.stats["chunks"] == 0     # no batched chunk ran
    src = g.edge_sources()
    assert not np.any(r.members[src] & r.members[g.indices])


# --------------------------------------------------------------------- #
# sharded execution under 4 forced host devices (subprocess lane)
# --------------------------------------------------------------------- #

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    assert jax.device_count() == 4, jax.device_count()
    import numpy as np
    from repro.core import serve as SV
    from repro.core.graph import Graph
    from repro.graphs.generators import gnm

    def ref_svc():
        # single-device, non-pipelined reference on the same process
        return SV.MWISService(SV.ServeConfig(
            backend="jnp", max_batch=8, devices=1, pipeline=False))

    def assert_same(a, b, tag):
        assert a.ok == b.ok, tag
        assert a.weight == b.weight, tag
        assert np.array_equal(a.members, b.members), tag

    # ragged mixed-cell batch: 10 instances over two cells, not a
    # multiple of the device count; includes the batch-of-1 spill chunk
    gs = [gnm(20 + 3 * i, 40 + 5 * i, seed=i) for i in range(8)]
    gs += [gnm(120, 300, seed=8), gnm(130, 320, seed=9)]
    want = ref_svc().solve_batch(gs)
    svc = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=8,
                                        devices=4))
    got = svc.solve_batch(gs)
    for a, b in zip(got, want):
        assert_same(a, b, "ragged-mixed")
    s = svc.stats
    assert s["devices"] == 4, s
    assert s["chunks"] > 0 and s["solve_errors"] == 0, s

    # batch of 1 on 4 devices: pads to one instance per device,
    # phantom results discarded
    one = SV.MWISService(SV.ServeConfig(backend="jnp", devices=4))
    assert_same(one.solve_one(gs[0]), want[0], "batch-of-1")

    # poisoned batchmate: the bad request errors, every healthy
    # batchmate stays bit-identical to the single-device reference
    nan_g = Graph(indptr=np.array([0, 1, 2]),
                  indices=np.array([1, 0], np.int32),
                  weights=np.array([np.nan, 1.0]))
    px = SV.MWISService(SV.ServeConfig(backend="jnp", max_batch=8,
                                       devices=4))
    pres = px.solve_batch([gs[0], nan_g, gs[1], gs[2]])
    assert not pres[1].ok and pres[1].reason == "bad_weight"
    for got_r, want_r in zip([pres[0], pres[2], pres[3]], want[:3]):
        assert_same(got_r, want_r, "poisoned")

    # blocked backend (stacked plans shard too)
    want_b = SV.MWISService(SV.ServeConfig(
        backend="blocked", max_batch=4, devices=1,
        pipeline=False)).solve_batch(gs[:4])
    got_b = SV.MWISService(SV.ServeConfig(
        backend="blocked", max_batch=4, devices=4)).solve_batch(gs[:4])
    for a, b in zip(got_b, want_b):
        assert_same(a, b, "blocked")

    # descent="auto" on a 4-device service: staged instances fall back
    # to the single-device per-instance path and match the reference
    cells = SV.serve_cells()
    big = cells[-1]
    dg = gnm(big.L // 2 + 8, big.L + 16, seed=33)
    d_want = SV.MWISService(SV.ServeConfig(
        backend="jnp", descent="auto", descent_min_L=big.L,
        devices=1, pipeline=False)).solve_batch([dg])[0]
    d_svc = SV.MWISService(SV.ServeConfig(
        backend="jnp", descent="auto", descent_min_L=big.L, devices=4))
    d_got = d_svc.solve_batch([dg])[0]
    assert_same(d_got, d_want, "descent-auto")
    assert d_svc.stats["descent_solves"] == 1

    print("SHARDED PARITY OK")
""")


@pytest.mark.slow
def test_sharded_serving_bit_identical_to_single_device():
    """The tentpole invariant: batch-axis sharding over a 4-device serve
    mesh (+ the host pipeline) is bit-identical per instance to the
    single-device path — across ragged/mixed/poisoned batches, both
    backends, and the descent fallback."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    )
    env.pop("XLA_FLAGS", None)          # the script forces its own
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT], capture_output=True,
        text=True, env=env, timeout=1800,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED PARITY OK" in r.stdout
