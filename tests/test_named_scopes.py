"""The program names its layers: ``mwis.*`` scopes in the compiled HLO's op
metadata of every program a benchmark cell runs, and ``mwis.*`` host spans
in a profiler trace, nested as the layers are."""

import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import distributed as D
from repro.core import engine as E
from repro.core import serve as SV
from repro.core import solvers as S
from repro.core.partition import partition_graph
from repro.graphs.generators import gnm

SCHEDULE = "cheap-fused"
RULES = {f"mwis.rule.{r}" for r in E.SCHEDULES[SCHEDULE].rules}
REDUCE = RULES | {"mwis.rule.heavy", "mwis.aggregate", "mwis.exchange",
                  "mwis.round.vote"}


def scopes_of(hlo_text: str) -> set:
    """The ``mwis.*`` components of every op_name in compiled HLO text."""
    return {c for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for c in re.findall(r"mwis\.[\w.]+", name)}


def config(**kw):
    return D.DisReduConfig(mode="async", stale_sweeps=2, schedule=SCHEDULE,
                           backend="blocked", **kw)


@pytest.fixture(scope="module")
def prob():
    pg = partition_graph(gnm(160, 480, seed=3), 2, window_cap=16,
                         common_cap=4)
    cfg = config()
    return pg, cfg, D.build_union_problem(pg, cfg.backend, cfg.r_blk)


def static(cfg, p):
    return dict(heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
                sweeps=cfg.sweeps_per_round, max_rounds=cfg.max_rounds, p=p,
                schedule=cfg.schedule, backend=cfg.backend)


def union_args(prob):
    return (prob.w0, prob.is_local, prob.is_ghost, prob.aux, prob.halo,
            prob.plan)


def test_union_reduce_program_carries_every_reduce_scope(prob):
    pg, cfg, pr = prob
    txt = D._disredu_union_jit.lower(
        *union_args(pr), **static(cfg, pr.p)).compile().as_text()
    assert scopes_of(txt) == REDUCE


@pytest.mark.parametrize("algo,extra", [
    ("rnp", {"mwis.peel"}),
    ("rg", set()),
])
def test_solver_programs_carry_their_scopes(prob, algo, extra):
    pg, cfg, pr = prob
    txt = S._solve_union_jit.lower(
        *union_args(pr), algo=algo, **static(cfg, pr.p)).compile().as_text()
    assert scopes_of(txt) == REDUCE | extra


def test_vmapped_serve_program_carries_the_solver_scopes():
    staged = []

    class Service(SV.MWISService):
        def _launch_chunk(self, st):
            staged.append((st, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), st.args)))
            return super()._launch_chunk(st)

    svc = Service(SV.ServeConfig(algo="rnp", backend="blocked",
                                 schedule=SCHEDULE))
    res = svc.solve_batch([gnm(20 + 3 * i, 40, seed=i) for i in range(3)])
    assert all(r.ok for r in res) and staged
    st, shapes = staged[0]
    fn = svc._batched_fn(st.cell, st.e_blk, st.backend)
    txt = fn.lower(*shapes).compile().as_text()
    assert scopes_of(txt) == REDUCE | {"mwis.peel"}


def test_chunks_are_stacked_on_the_host():
    # device-side stacking would queue behind the chunk in flight
    svc = SV.MWISService(SV.ServeConfig(algo="rnp", backend="blocked"))
    g = gnm(20, 40, seed=1)
    cell = SV.bucket_for(g.n, g.num_directed_edges, svc.cells)
    topos, good = svc._pack_requests(cell, [0], [g], [None], "blocked")
    args, _, bt = svc._stack_chunk(cell, topos, "blocked")
    leaves = jax.tree.leaves(args)
    assert good == [0] and leaves
    assert all(isinstance(x, np.ndarray) and x.shape[0] == bt
               for x in leaves)


def test_union_pack_is_host_work_and_the_upload_places_it():
    pg = partition_graph(gnm(60, 150, seed=6), 2, window_cap=16,
                         common_cap=4)
    host = D.pack_union_problem(pg, "blocked", 8)
    arrays = [x for x in jax.tree.leaves(host) if not isinstance(x, int)]
    assert arrays and all(isinstance(x, np.ndarray) for x in arrays)
    dev = D.upload_union_problem(host)
    ref = D.build_union_problem(pg, "blocked", 8)
    assert (dev.p, dev.V) == (ref.p, ref.V) == (host.p, host.V)
    for a, b in zip(jax.tree.leaves(dev), jax.tree.leaves(ref)):
        if isinstance(a, int):
            assert a == b
            continue
        assert isinstance(a, jax.Array) and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_serve_topology_cache_holds_the_host_pack():
    svc = SV.MWISService(SV.ServeConfig(algo="rnp", backend="blocked"))
    g = gnm(20, 40, seed=2)
    cell = SV.bucket_for(g.n, g.num_directed_edges, svc.cells)
    topo = svc._topology(g, cell, "blocked")
    arrays = [x for x in jax.tree.leaves(topo.prob) if not isinstance(x, int)]
    assert arrays and all(isinstance(x, np.ndarray) for x in arrays)


SHARD_MAP = textwrap.dedent(r"""
    import json, re
    import jax
    from repro.core import distributed as D
    from repro.core.partition import partition_graph
    from repro.graphs.generators import gnm
    from repro.launch.mesh import make_host_mesh

    pg = partition_graph(gnm(240, 720, seed=4), 4, window_cap=16,
                         common_cap=4)
    cfg = D.DisReduConfig(mode="async", stale_sweeps=2,
                          schedule="cheap-fused", backend="blocked",
                          exchange="allgather")
    mesh = make_host_mesh(4)
    run, _ = D.disredu_shard_map_fn(pg, cfg, mesh)
    arrays = D.place_on_mesh(D.shard_map_arrays(pg, cfg), mesh)
    txt = jax.jit(run).lower(arrays).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', txt)
    print(json.dumps(sorted({c for n in names
                             for c in re.findall(r"mwis\.[\w.]+", n)})))
""")


def test_four_pe_shard_map_program_carries_every_reduce_scope():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SHARD_MAP], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert set(json.loads(out.stdout.strip().splitlines()[-1])) == REDUCE


# --------------------------------------------------------------------- #
# host spans
# --------------------------------------------------------------------- #
def host_spans(trace_dir):
    """[(name, start ns, end ns)] of the ``mwis.*`` host events."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    data = ProfileData.from_file(path)
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("mwis."))


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_reduce_call_host_spans_nest(tmp_path):
    g = gnm(160, 480, seed=5)
    cfg = config()
    with jax.profiler.trace(str(tmp_path)):
        pg = partition_graph(g, 2, window_cap=16, common_cap=4)
        state, _, _ = D.disredu(pg, cfg)
        state.w.block_until_ready()
    spans = host_spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert {k: len(v) for k, v in by.items()} == {
        "mwis.reduce.pack": 1, "mwis.reduce.plan": 1,
        "mwis.reduce.upload": 1}
    (pack,), (plan,), (upload,) = (
        by[k] for k in ("mwis.reduce.pack", "mwis.reduce.plan",
                        "mwis.reduce.upload"))
    assert inside(plan, pack) and inside(upload, pack)
    assert plan[2] <= upload[1]


def test_serve_host_spans_feed_the_stage_times(tmp_path):
    graphs = [gnm(18 + 2 * i, 40, seed=90 + i) for i in range(6)]
    svc = SV.MWISService(SV.ServeConfig(backend="blocked", max_batch=2,
                                        pipeline=True, verify="full"))
    svc.solve_batch(graphs)     # compile and cache outside the trace
    before = dict(svc.stats["stage_ms"])
    with jax.profiler.trace(str(tmp_path)):
        res = svc.solve_batch(graphs)
    assert all(r.ok for r in res)
    spans = host_spans(str(tmp_path))
    total = {}
    for name, s, e in spans:
        total[name] = total.get(name, 0.0) + (e - s) / 1e6
    assert set(total) == {"mwis.serve.pack", "mwis.serve.stage",
                          "mwis.serve.solve", "mwis.serve.fetch",
                          "mwis.serve.verify"}
    after = svc.stats["stage_ms"]
    for stage, span in (("pack", "pack"), ("transfer", "stage"),
                        ("solve", "solve"), ("fetch", "fetch")):
        got = after[stage] - before[stage]
        assert got == pytest.approx(total["mwis.serve." + span],
                                    rel=0.05, abs=1.0), stage
    solves = [s for s in spans if s[0] == "mwis.serve.solve"]
    fetches = [s for s in spans if s[0] == "mwis.serve.fetch"]
    assert len(solves) == len(fetches) == 3
    # each chunk's fetch starts once its solve span has ended
    for sv, ft in zip(solves, fetches):
        assert sv[2] <= ft[1]


def test_span_adds_its_time_once():
    from repro.core.spans import span

    rec = {}
    sp = span("mwis.serve.solve", rec, "solve_ms").open()
    sp.close()
    took = rec["solve_ms"]
    sp.close()
    assert took > 0 and rec == {"solve_ms": took}


def test_an_escaping_error_closes_the_solve_span_in_flight():
    opened = []

    class Service(SV.MWISService):
        def _launch_chunk(self, st):
            inflight = super()._launch_chunk(st)
            opened.append(inflight.solving)
            return inflight

        def _dispatch_chunk(self, cell, idxs, graphs, out):
            if opened:
                raise RuntimeError("lost the host")
            return super()._dispatch_chunk(cell, idxs, graphs, out)

    svc = Service(SV.ServeConfig(backend="blocked", max_batch=2,
                                 pipeline=True))
    with pytest.raises(RuntimeError, match="lost the host"):
        svc.solve_batch([gnm(18 + 2 * i, 40, seed=70 + i)
                         for i in range(4)])
    (solving,) = opened
    assert solving._ann is None and solving.rec["solve_ms"] > 0
