"""Aggregate-engine benchmark: sweep + solver-round timings per backend →
BENCH_engine.json.

Times, on the paper's generator families:

  * ONE reduction sweep (the engine's unit of work: aggregate computation
    + all scheduled rule families) under

      - the seed-semantics reference (frozen oracle, fused sweep, jnp ops),
      - the engine jnp backend        (op-identical to the seed — the
                                       no-regression check),
      - the engine blocked backend at every R_BLK candidate (blocked-ELL
        layout, jnp block kernels); ``blocked`` is the fixed R_BLK=8
        baseline and ``blocked-auto`` the measured best over the candidate
        table — the plan-build-time autotune record,
      - the engine pallas backend     (fused multi-payload kernel; off the
        TPU it runs in interpret mode, asked for with
        REPRO_PALLAS_INTERPRET=1, so only a small instance — interpret
        timings measure correctness plumbing, not TPU performance);

  * ONE greedy round (weighted-Luby step + halo exchange) and ONE RnP round
    (rule sweep + exchange + peel) per backend — the solver hot loops that
    re-enter reduction many times per run, now routed through the same
    aggregate layer.

Emits BENCH_engine.json so the perf trajectory of the hot path is recorded
per PR.  Run via ``python benchmarks/run.py --engine-only`` (``--engine-
small`` for the CI-sized variant).
"""

from __future__ import annotations

import json
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _time_interleaved(entries, reps: int = 30) -> dict:
    """entries: {label: (fn, state)} → min-of-reps us, reps interleaved
    across labels so machine noise hits every backend equally."""
    for fn, state in entries.values():
        jax.block_until_ready(fn(state))  # compile
        jax.block_until_ready(fn(state))  # warm
    best = {label: float("inf") for label in entries}
    for _ in range(reps):
        for label, (fn, state) in entries.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(state))
            best[label] = min(best[label], time.perf_counter() - t0)
    return {label: round(us * 1e6, 1) for label, us in best.items()}


def _bench_graph(name, g, p, *, schedule: str, with_pallas: bool,
                 seed_oracle=None, reps: int = 30,
                 candidates: Optional[Tuple[int, ...]] = None) -> dict:
    from repro.core import distributed as D, engine as E, rules as R
    from repro.core import partition as part
    from repro.core import solvers as SOL

    # the fixed R_BLK baseline must always be in the candidate table: it is
    # the "blocked" label and the floor the autotune is judged against
    candidates = tuple(sorted(
        set(candidates or E.R_BLK_CANDIDATES) | {E.R_BLK}
    ))
    row = {"graph": name, "n": g.n, "m": g.m, "p": p, "schedule": schedule}
    pg = part.partition_graph(g, p, window_cap=12)

    probs = {"jnp": D.build_union_problem(pg, "jnp")}
    for c in candidates:
        probs[f"blocked-r{c}"] = D.build_union_problem(pg, "blocked", c)

    def sweep_entry(backend, prob):
        fn = jax.jit(lambda s, _aux=prob.aux, _pl=prob.plan, _b=backend:
                     E.sweep(s, _aux, schedule=schedule, backend=_b,
                             plan=_pl))
        return fn, R.init_state(prob.w0, prob.is_local, prob.is_ghost)

    entries = {"jnp": sweep_entry("jnp", probs["jnp"])}
    cand_label = {}
    for c in candidates:
        # fixed-block baseline keeps its historical label "blocked"
        label = "blocked" if c == E.R_BLK else f"blocked-r{c}"
        cand_label[c] = label
        entries[label] = sweep_entry("blocked", probs[f"blocked-r{c}"])
    if with_pallas:
        label = "pallas-interpret" if jax.default_backend() != "tpu" \
            else "pallas"
        entries[label] = sweep_entry(
            "pallas", probs[f"blocked-r{E.R_BLK}"]
        )
    if seed_oracle is not None:
        prob = probs["jnp"]
        state0 = seed_oracle.init_state(
            prob.w0, prob.is_local, prob.is_ghost
        )
        entries["seed-fused-jnp"] = (
            jax.jit(lambda s, _aux=prob.aux:
                    seed_oracle.sweep_cheap_fused(s, _aux)),
            state0,
        )
    sweep_us = _time_interleaved(entries, reps=reps)
    # measured autotune: best candidate over the table (includes the fixed
    # baseline, so blocked-auto is never slower than blocked by
    # construction); the analytic pick is recorded for comparison
    best_c = min(candidates, key=lambda c: sweep_us[cand_label[c]])
    sweep_us["blocked-auto"] = sweep_us[cand_label[best_c]]
    row["per_sweep_us"] = sweep_us
    row["blocked_auto"] = {
        "r_blk": best_c,
        "analytic_r_blk": E.autotune_r_blk(
            jax.device_get(probs["jnp"].aux.row), pg.p * pg.V, candidates
        ),
    }

    # --- solver rounds per backend ------------------------------------ #
    # the blocked rounds run the autotuned plan and say so in the label
    # (the "blocked" sweep label above is the fixed R_BLK=8 baseline)
    round_backends = [("jnp", probs["jnp"]),
                      ("blocked-auto", probs[f"blocked-r{best_c}"])]
    if with_pallas:
        round_backends.append(
            ("pallas-interpret" if jax.default_backend() != "tpu"
             else "pallas", probs[f"blocked-r{best_c}"])
        )

    greedy_entries, rnp_entries = {}, {}
    for label, prob in round_backends:
        backend = label.split("-")[0]  # blocked-auto / pallas-interpret
        ctx = SOL._union_ctx(prob, backend)
        state0 = R.init_state(prob.w0, prob.is_local, prob.is_ghost)

        def greedy_round(s, _aux=prob.aux, _pl=prob.plan, _b=backend,
                         _ctx=ctx):
            s = SOL.greedy_step(s, _aux, backend=_b, plan=_pl)
            return _ctx.exchange(s)[0]

        def rnp_round(s, _aux=prob.aux, _pl=prob.plan, _b=backend,
                      _ctx=ctx):
            s = E.sweep(s, _aux, schedule=schedule, backend=_b, plan=_pl)
            s = _ctx.exchange(s)[0]
            score = SOL.peel_score(s, _aux, backend=_b, plan=_pl)
            return _ctx.peel(s, score)

        greedy_entries[label] = (jax.jit(greedy_round), state0)
        rnp_entries[label] = (jax.jit(rnp_round), state0)
    row["greedy_round_us"] = _time_interleaved(greedy_entries, reps=reps)
    row["rnp_round_us"] = _time_interleaved(rnp_entries, reps=reps)
    return row


def _bench_descent(small: bool = False) -> dict:
    """Fixed-shape vs shape-descent end-to-end greedy solve on a
    serve_m-sized instance (the ISSUE's target cell), plus the per-round
    alive-vertex/stage-time trajectory of both paths.

    The trajectory rows come from ``solve_staged(..., trajectory=True)``
    with one-round stages — an empty ladder keeps the fixed path at the
    input shape while still reporting per-round alive counts.  The timed
    comparison runs each path monolithically (no per-round readback), and
    asserts the two member masks are bit-identical.
    """
    import numpy as np

    from repro.configs import base as CFG
    from repro.core import distributed as D
    from repro.core import partition as part
    from repro.core import solvers as SOL
    from repro.core.graph import from_edge_list
    from repro.graphs import generators as gen

    cell = CFG.MWIS_SHAPES["serve_m"]
    n = int(cell["L"] * 0.8)
    # bulk + hard kernel: a random bulk that greedy decides in a couple of
    # rounds, plus a weight-ramp path whose greedy frontier advances ~one
    # vertex per round — the motivating serve_m workload (the kernel
    # collapses to a small fraction fast, then the solver grinds on it)
    n_kernel = 200
    n_bulk = n - n_kernel
    bulk = gen.gnm(n_bulk, 3 * n_bulk, seed=11)
    bsrc = bulk.edge_sources()
    und = bsrc < bulk.indices
    pairs = np.stack([bsrc[und], bulk.indices[und]], axis=1).astype(np.int64)
    chain = np.arange(n_bulk, n - 1, dtype=np.int64)
    pairs = np.concatenate(
        [pairs, np.stack([chain, chain + 1], axis=1)], axis=0)
    weights = np.concatenate([
        np.asarray(bulk.weights, np.int64),
        np.arange(1, n_kernel + 1, dtype=np.int64),   # the ramp
    ]).astype(np.int32)
    g = from_edge_list(n, pairs, weights)
    pad = dict(L=cell["L"], E=cell["E"], G=cell["G"], B=cell["B"],
               S=cell["S"])
    algo, p = "greedy", 1
    pg = part.partition_graph(g, p, window_cap=cell["D"],
                              common_cap=cell["Dc"], pad_to=pad)
    cfg_fixed = D.DisReduConfig(mode="sync", heavy_k=8)
    cfg_desc = D.DisReduConfig(mode="sync", heavy_k=8, descent=True,
                               descent_every=2)
    cfg_traj = D.DisReduConfig(mode="sync", heavy_k=8, descent=True,
                               descent_every=1)

    def run(cfg, **kw):
        return SOL.solve_staged(g, p, algo, cfg, pg=pg, **kw)

    # per-round trajectories (stage = 1 round; empty ladder = never move)
    _, st_tf = run(cfg_traj, ladder=(), trajectory=True)
    _, st_td = run(cfg_traj, trajectory=True)

    # end-to-end timing, warm then min-of-reps (same topology → plan
    # cache + jit caches hot, exactly the serving steady state)
    reps = 2 if small else 4
    m_fixed, _ = run(cfg_fixed)
    m_desc, st_d = run(cfg_desc)
    t_fixed = t_desc = float("inf")
    for _ in range(reps):
        _, st = run(cfg_fixed)
        t_fixed = min(t_fixed, st["t_total"])
        _, st = run(cfg_desc)
        t_desc = min(t_desc, st["t_total"])
    assert (m_fixed == m_desc).all(), \
        "shape descent changed the greedy solution"

    # descent plan reuse: run the blocked-backend descent path twice on one
    # shared PlanCache — the second solve's descent plans must all hit
    from repro.core import engine as E
    cache = E.PlanCache(max_entries=64)
    cfg_blk = D.DisReduConfig(mode="sync", heavy_k=8, backend="blocked",
                              descent=True, descent_every=2)
    m_blk, _ = SOL.solve_staged(g, p, algo, cfg_blk, pg=pg,
                                plan_cache=cache)
    SOL.solve_staged(g, p, algo, cfg_blk, pg=pg, plan_cache=cache)
    assert (m_blk == m_fixed).all(), \
        "blocked-backend descent diverged from jnp"
    cs = cache.stats
    return {
        "graph": f"bulk_ramp_n{n}", "n": g.n, "m": g.m, "p": p,
        "algo": algo, "cell": "serve_m",
        "fixed_us": round(t_fixed * 1e6, 1),
        "descent_us": round(t_desc * 1e6, 1),
        "speedup": round(t_fixed / max(t_desc, 1e-9), 2),
        "descents": st_d["descents"],
        "path": [e["cell"] for e in st_d["path"]],
        "bit_identical": True,
        "plan_cache": {
            "hits": cs.hits, "misses": cs.misses,
            "descent_hits": cs.descent_hits,
            "descent_misses": cs.descent_misses,
        },
        "trajectory_fixed": st_tf["stages"],
        "trajectory_descent": st_td["stages"],
    }


def run_engine_bench(out_path: str = "BENCH_engine.json",
                     seed_oracle=None, small: bool = False) -> dict:
    from repro.graphs import generators as gen

    results = []
    if not small:
        for fam, n in (("gnm", 2000), ("rgg", 2000), ("rhg", 1500)):
            g = gen.FAMILIES[fam](n, seed=7)
            results.append(_bench_graph(
                f"{fam}_n{n}", g, 4, schedule="cheap-fused",
                with_pallas=False, seed_oracle=seed_oracle,
            ))
    # pallas path: interpret mode is orders slower than compiled — bench a
    # small instance only, as a plumbing/latency record (TPU numbers TBD).
    # This is also the whole CI-sized (small=True) run.
    g = gen.FAMILIES["rgg"](300, seed=7)
    results.append(_bench_graph(
        "rgg_n300_small", g, 2, schedule="cheap-fused", with_pallas=True,
        seed_oracle=seed_oracle if small else None,
        reps=5 if small else 30,
        candidates=(8, 16) if small else None,
    ))
    payload = {
        "meta": {
            "unit": "us per reduction sweep (aggregates + all scheduled "
                    "rule families) / per solver round, union path",
            "jax": jax.__version__,
            "device": jax.default_backend(),
            "small": small,
            "note": "engine jnp backend is op-identical to the seed sweep "
                    "(bit-parity: tests/test_engine_parity.py); "
                    "seed-fused-jnp rows time the frozen seed oracle "
                    "directly — the no-regression reference; 'blocked' is "
                    "the fixed R_BLK=8 baseline, 'blocked-auto' the "
                    "measured best over the R_BLK candidate table "
                    "(plan-build-time autotune); greedy_round_us / "
                    "rnp_round_us time one solver round (step + halo "
                    "exchange [+ peel]) per backend, blocked rounds on "
                    "the autotuned plan; 'descent' compares the "
                    "fixed-shape vs shape-descent end-to-end greedy solve "
                    "on a serve_m-sized instance (bit-identical members) "
                    "with per-round alive/time trajectories",
        },
        "results": results,
        "descent": _bench_descent(small=small),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload
