"""Distributed MWIS solvers (§6): GS/GA, RGS/RGA, RnPS/RnPA.

  * greedy (GS/GA)          — distributed weighted Luby: a vertex joins the
    solution iff its (weight, gid) is lexicographically maximal over its
    active neighborhood; border synchronized every round; PE-rank/id
    tie-breaking.  Deterministic == sequential priority greedy
    (`sequential.solve_greedy` is the oracle).
  * reduce-and-greedy (RGS/RGA) — DisRedu{S,A} to the global fixpoint, then
    greedy on the kernel.
  * reduce-and-peel (RnPS/RnPA) — loop { reduce to fixpoint; every PE peels
    its locally worst vertex argmax ω(N(v)) − ω(v) } until empty (HtWIS
    criterion, one peel per PE per round as in the paper).

All algorithms are expressed once over abstract collectives and instantiated
for the union (single-device simulation) and shard_map (production) paths.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as E
from repro.core import exchange as X
from repro.core import rules as R
from repro.core.distributed import (
    DisReduConfig, UnionProblem, _unpack_per_pe, build_union_problem,
    place_on_mesh, shard_map_arrays,
)
from repro.core.local_reduce import local_reduce
from repro.core.partition import PartitionedGraph
from repro.core.spans import span

UNDECIDED, INCLUDED, EXCLUDED, FOLDED = 0, 1, 2, 3
I32_MIN = jnp.iinfo(jnp.int32).min


class Ctx(NamedTuple):
    """Abstract SPMD context: exchange + global-any + per-PE peel."""

    exchange: Callable  # state -> (state, changed)
    gany: Callable      # bool scalar -> bool scalar (global OR)
    peel: Callable      # (state, score [V]) -> state  (one peel per PE)


# --------------------------------------------------------------------- #
# algorithm bodies (layout-agnostic)
# --------------------------------------------------------------------- #
def _reduce_to_fixpoint(state, aux, ctx: Ctx, cfg: DisReduConfig,
                        plan=None):
    def body(carry):
        state, rounds, _ = carry
        snap_s, snap_w = state.status, state.w
        state = local_reduce(
            state, aux, heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
            max_sweeps=cfg.sweeps_per_round, schedule=cfg.schedule,
            backend=cfg.backend, plan=plan,
        )
        state, _ = ctx.exchange(state)
        with jax.named_scope("mwis.round.vote"):
            changed = ctx.gany(
                (state.status != snap_s).any() | (state.w != snap_w).any()
            )
        return state, rounds + 1, changed

    def cond(carry):
        _, rounds, changed = carry
        return changed & (rounds < cfg.max_rounds)

    state, rounds, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int32), jnp.ones((), bool))
    )
    return state, rounds


def greedy_step(state, aux, *, backend: str = "jnp", plan=None):
    """One weighted-Luby round (no exchange): include every local active
    vertex no active neighbor beats.

    The seed's two reductions (max neighbor weight + min gid among the
    argmaxes) collapse into ONE lexicographic beat test per edge — v wins
    iff no neighbor u has (w[u], -gid[u]) lexicographically above
    (w[v], -gid[v]) — so a greedy round costs a single pass through the
    aggregate backend.  Gids are unique, hence this equals the seed's
    (w > mw) | (w == mw & gid < mg) winner set bit for bit, which is the
    ``sequential.solve_greedy`` oracle semantics.
    """
    V = aux.gid.shape[0]
    active = state.status == UNDECIDED
    eact = active[aux.row] & active[aux.col]
    wc, wr = state.w[aux.col], state.w[aux.row]
    beat_e = eact & (
        (wc > wr) | ((wc == wr) & (aux.gid[aux.col] < aux.gid[aux.row]))
    )
    _, beaten, _, _ = E.aggregate(
        aux.row, V, data_max=beat_e.astype(jnp.int32),
        backend=backend, plan=plan,
    )
    win = aux.is_local & active & (beaten <= 0)
    return R._apply_include(state, aux, eact, win)


def _greedy_rounds(state, aux, ctx: Ctx, max_rounds: int = 100_000,
                   *, backend: str = "jnp", plan=None):
    """Weighted-Luby rounds until no vertex is UNDECIDED anywhere;
    returns (state, rounds)."""

    def body(carry):
        state, rounds, _ = carry
        state = greedy_step(state, aux, backend=backend, plan=plan)
        state, _ = ctx.exchange(state)
        remaining = ctx.gany((aux.is_local & (state.status == UNDECIDED)).any())
        return state, rounds + 1, remaining

    def cond(carry):
        _, rounds, remaining = carry
        return remaining & (rounds < max_rounds)

    remaining0 = ctx.gany((aux.is_local & (state.status == UNDECIDED)).any())
    state, iters, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int32), remaining0)
    )
    return state, iters


def peel_score(state, aux, *, backend: str = "jnp", plan=None):
    """[V] HtWIS peel score ω(N(v)) − ω(v) for local active vertices
    (I32_MIN elsewhere), through the aggregate backend."""
    V = aux.gid.shape[0]
    active = state.status == UNDECIDED
    eact = active[aux.row] & active[aux.col]
    aw = jnp.where(active, state.w, 0)
    s, _, _, _ = E.aggregate(
        aux.row, V, data_sum=jnp.where(eact, aw[aux.col], 0),
        backend=backend, plan=plan,
    )
    return jnp.where(aux.is_local & active, s - state.w, I32_MIN)


def _rnp_loop(state, aux, ctx: Ctx, cfg: DisReduConfig,
              max_peels: int = 1_000_000, plan=None):
    """reduce → peel-one-per-PE → repeat until globally empty (§6);
    returns (state, peel iterations)."""

    def body(carry):
        state, it, _ = carry
        state, _ = _reduce_to_fixpoint(state, aux, ctx, cfg, plan=plan)
        with jax.named_scope("mwis.peel"):
            score = peel_score(state, aux, backend=cfg.backend, plan=plan)
            state = ctx.peel(state, score)
        remaining = ctx.gany((aux.is_local & (state.status == UNDECIDED)).any())
        return state, it + 1, remaining

    def cond(carry):
        _, it, remaining = carry
        return remaining & (it < max_peels)

    remaining0 = ctx.gany((aux.is_local & (state.status == UNDECIDED)).any())
    state, iters, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int32), remaining0)
    )
    return state, iters


def run_algorithm(state, aux, ctx: Ctx, cfg: DisReduConfig, algo: str,
                  plan=None):
    """algo ∈ {reduce, greedy, rg, rnp} → (final state, iterations).

    All local vertices are decided for the solver algos; the kernel remains
    for 'reduce'.  Iterations count the algorithm's outer loop: reduce
    rounds ('reduce'), weighted-Luby rounds ('greedy', and 'rg' after its
    reduce), peel iterations ('rnp')."""
    if algo == "reduce":
        state, iters = _reduce_to_fixpoint(state, aux, ctx, cfg, plan=plan)
    elif algo == "greedy":
        state, iters = _greedy_rounds(state, aux, ctx, backend=cfg.backend,
                                      plan=plan)
    elif algo == "rg":
        state, _ = _reduce_to_fixpoint(state, aux, ctx, cfg, plan=plan)
        state, iters = _greedy_rounds(state, aux, ctx, backend=cfg.backend,
                                      plan=plan)
    elif algo == "rnp":
        state, iters = _rnp_loop(state, aux, ctx, cfg, plan=plan)
    else:
        raise ValueError(f"unknown algo {algo!r}")
    return state, iters


# --------------------------------------------------------------------- #
# union instantiation (single-device SPMD simulation)
# --------------------------------------------------------------------- #
def _union_ctx(prob: UnionProblem, backend: str = "jnp") -> Ctx:
    p, V = prob.p, prob.w0.shape[0] // prob.p

    def exch(state):
        return X.exchange_union(
            state, prob.aux, prob.halo, p=p,
            backend=backend, plan=prob.plan,
        )

    def peel(state, score):
        sc = score.reshape(p, V)
        top = jnp.argmax(sc, axis=1)
        has = sc[jnp.arange(p), top] > I32_MIN
        flat = jnp.where(has, top + jnp.arange(p) * V, p * V - 1)
        # excluding the per-PE argmax; nil slot absorbs empty PEs
        status = state.status.at[flat].set(
            jnp.where(has, jnp.int8(EXCLUDED), jnp.int8(EXCLUDED))
        )
        # nil slots are EXCLUDED already, so unconditional set is safe
        return state._replace(status=status)

    return Ctx(exchange=exch, gany=lambda x: x, peel=peel)


def solve_union_arrays(w0, is_local, is_ghost, aux, halo, plan, *, algo,
                       heavy_k, use_heavy, sweeps, max_rounds, p,
                       schedule="cheap", backend="jnp"):
    """Traceable union-path solve body: arrays in, (state, members,
    iterations) out (iterations as :func:`run_algorithm` counts them).

    This is the batch-axis seam of the serving layer: every argument is a
    plain array pytree (no host-side build), so ``jax.vmap`` over a leading
    instance axis yields the batched many-instance solver, and the
    single-instance jit below is the same trace with the axis dropped.
    Keyword arguments must be trace-static.
    """
    prob = UnionProblem(w0, is_local, is_ghost, aux, halo, p, 0, plan)
    cfg = DisReduConfig(
        heavy_k=heavy_k, use_heavy=use_heavy,
        mode="sync" if sweeps >= 1_000_000 else "async",
        stale_sweeps=sweeps, max_rounds=max_rounds, schedule=schedule,
        backend=backend,
    )
    ctx = _union_ctx(prob, backend)
    state = R.init_state(w0, is_local, is_ghost)
    state, iters = run_algorithm(state, aux, ctx, cfg, algo, plan=plan)
    members = R.reconstruct_members(state, aux)
    return state, members, iters


_solve_union_jit = functools.partial(
    jax.jit,
    static_argnames=("algo", "heavy_k", "use_heavy", "sweeps", "max_rounds",
                     "p", "schedule", "backend"),
)(solve_union_arrays)


def solve(
    pg: PartitionedGraph,
    algo: str,
    cfg: DisReduConfig = DisReduConfig(),
) -> Tuple[np.ndarray, R.RedState, int]:
    """Solve MWIS heuristically; returns (global member mask, final state,
    iterations as :func:`run_algorithm` counts them).

    algo: 'greedy' (GS/GA), 'rg' (RGS/RGA), 'rnp' (RnPS/RnPA) — the S/A
    flavour is chosen by cfg.mode ('sync'/'async').
    """
    prob = build_union_problem(pg, cfg.backend, cfg.r_blk)
    state, in_set, iters = _solve_union_jit(
        prob.w0, prob.is_local, prob.is_ghost, prob.aux, prob.halo,
        prob.plan,
        algo=algo, heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
        sweeps=cfg.sweeps_per_round, max_rounds=cfg.max_rounds, p=prob.p,
        schedule=cfg.schedule, backend=cfg.backend,
    )
    members = np.zeros(pg.n_global, dtype=bool)
    sel = np.asarray(in_set) & np.asarray(prob.is_local)
    members[np.asarray(prob.aux.gid)[sel]] = True
    return members, state, int(iters)


# --------------------------------------------------------------------- #
# staged solve with adaptive shape descent (kernel compaction)
# --------------------------------------------------------------------- #
class LadderCell(NamedTuple):
    """One rung of the static shape ladder (serve/descent MWIS_SHAPES
    cells, or ad-hoc test cells).  L/E gate admission; G/B/S floor the
    halo pads (the exact per-PE maxima override them); r_blk picks the
    blocked-ELL row-block height for plans packed at this rung."""

    name: str
    L: int
    E: int
    G: int = 4
    B: int = 4
    S: int = 4
    r_blk: Optional[int] = None


def default_ladder() -> Tuple[LadderCell, ...]:
    """The configured descent ladder: serve cells + descent extensions
    from ``configs.base.MWIS_SHAPES``, ascending."""
    from repro.configs import base as CFG

    cells = []
    for name in CFG.MWIS_DESCENT_LADDER:
        m = CFG.MWIS_SHAPES[name]
        cells.append(LadderCell(
            name=name, L=m["L"], E=m["E"], G=m["G"], B=m["B"], S=m["S"],
            r_blk=m.get("seg_blk", {}).get("r_blk"),
        ))
    return tuple(sorted(cells, key=lambda c: (c.L, c.E)))


class _Frame(NamedTuple):
    """Pre-descent snapshot: the full-shape state (with its fold log) and
    the aux needed to replay reconstruction at that level."""

    state: R.RedState
    aux: R.Aux
    is_local: jax.Array


@functools.partial(
    jax.jit,
    static_argnames=("phase", "iters", "heavy_k", "use_heavy", "sweeps",
                     "p", "schedule", "backend"),
)
def _stage_union_jit(state, is_ghost, aux, halo, plan, *, phase, iters,
                     heavy_k, use_heavy, sweeps, p, schedule, backend):
    """One bounded solver stage on the union layout.

    phase='reduce' — ≤ `iters` DisRedu rounds; returns (state, rounds,
    changed_last) so the host loop can tell fixpoint (changed False) from
    budget exhaustion even at iters=1.
    phase='greedy' — ≤ `iters` weighted-Luby rounds; returns (state,
    rounds, remaining).
    phase='peel'   — exactly one HtWIS peel per PE (no exchange! ghosts
    are stale until the next reduce round's exchange, which is why the
    staged driver never descends right after a peel).

    Resuming a phase across stage boundaries is exact: reduce rounds are
    idempotent at fixpoint, greedy re-evaluates `remaining` from the
    statuses, and the rnp loop body is reduce-to-fixpoint + peel — so
    chunked execution visits bit-identical states to the monolithic
    while_loops in :func:`run_algorithm`.
    """
    prob = UnionProblem(state.w, aux.is_local, is_ghost, aux, halo, p, 0,
                        plan)
    ctx = _union_ctx(prob, backend)
    if phase == "reduce":
        cfg = DisReduConfig(
            heavy_k=heavy_k, use_heavy=use_heavy,
            mode="sync" if sweeps >= 1_000_000 else "async",
            stale_sweeps=sweeps, schedule=schedule, backend=backend,
            max_rounds=iters,
        )

        def body(carry):
            state, rounds, _ = carry
            snap_s, snap_w = state.status, state.w
            state = local_reduce(
                state, aux, heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
                max_sweeps=cfg.sweeps_per_round, schedule=cfg.schedule,
                backend=cfg.backend, plan=plan,
            )
            state, _ = ctx.exchange(state)
            with jax.named_scope("mwis.round.vote"):
                changed = ((state.status != snap_s).any()
                           | (state.w != snap_w).any())
            return state, rounds + 1, changed

        def cond(carry):
            _, rounds, changed = carry
            return changed & (rounds < iters)

        return jax.lax.while_loop(
            cond, body, (state, jnp.zeros((), jnp.int32), jnp.ones((), bool))
        )
    if phase == "greedy":
        def body(carry):
            state, rounds, _ = carry
            state = greedy_step(state, aux, backend=backend, plan=plan)
            state, _ = ctx.exchange(state)
            remaining = (aux.is_local & (state.status == UNDECIDED)).any()
            return state, rounds + 1, remaining

        def cond(carry):
            _, rounds, remaining = carry
            return remaining & (rounds < iters)

        remaining0 = (aux.is_local & (state.status == UNDECIDED)).any()
        return jax.lax.while_loop(
            cond, body, (state, jnp.zeros((), jnp.int32), remaining0)
        )
    if phase != "peel":
        raise ValueError(f"unknown stage phase {phase!r}")
    with jax.named_scope("mwis.peel"):
        score = peel_score(state, aux, backend=backend, plan=plan)
        state = ctx.peel(state, score)
    remaining = (aux.is_local & (state.status == UNDECIDED)).any()
    return state, jnp.zeros((), jnp.int32), remaining


#: Host-side stitching calls reconstruction once per descent level; jit it
#: (the monolithic path compiles it into the solve program).
_reconstruct_jit = jax.jit(R.reconstruct_members)


def _pick_cell(ladder, need, cur_L, cur_E, factor):
    """Smallest ladder cell the kernel fits that is a real descent
    (hysteresis: cell.L * factor <= current L, never grow E)."""
    for c in sorted(ladder, key=lambda c: (c.L, c.E)):
        if (c.L * max(factor, 1) <= cur_L and c.E <= cur_E
                and c.L >= need["L"] and c.E >= need["E"]):
            return c
    return None


def solve_staged(
    g,
    p: int,
    algo: str,
    cfg: DisReduConfig = DisReduConfig(),
    *,
    ladder=None,
    plan_cache: Optional[E.PlanCache] = None,
    pad_to=None,
    window_cap: int = 16,
    common_cap: int = 4,
    edge_balanced: bool = True,
    ckpt=None,
    resume: bool = False,
    on_descent=None,
    trajectory: bool = False,
    pg: Optional[PartitionedGraph] = None,
) -> Tuple[np.ndarray, dict]:
    """Staged solve with adaptive **shape descent** (kernel compaction).

    Replaces the old two-phase ``solve_compact`` experiment.  The solve
    runs in bounded *stages* (``cfg.descent_every`` rounds each); at every
    post-exchange stage boundary the alive kernel is measured
    (:func:`distributed.kernel_shape`) and, when it fits a smaller rung of
    the static shape `ladder` with hysteresis ``cfg.descent_factor``, the
    partition is *restricted* onto that cell
    (:func:`partition.compact_partition`), re-packed through
    ``engine.plan_for`` (descent plans hit the topology-keyed PlanCache,
    tagged in ``PlanCacheStats.descent_*``), and the solve continues at
    the smaller shape — so late rounds pay for the kernel, not the input.

    Bit-identity: compaction is an exact restriction (preserved ownership,
    window positions, gids), stage chunking visits the same states as the
    monolithic loops, and decisions stitch back through the per-level fold
    logs — members equal :func:`solve` on the same partition, bit for bit
    (for every algo/backend/schedule; descent off ⇒ literally one stage).

    ``ckpt`` (a ``distributed.checkpoint.CheckpointManager``) saves the
    frame stack + current state at every descent boundary; ``resume=True``
    restores the latest boundary and replays the deterministic compaction
    chain host-side before continuing.  ``on_descent(descents, cell_name)``
    is the test/fault seam, called after each committed descent.

    Returns ``(global member mask, stats)`` with stats keys: descents,
    path, kernel_ratio, alive_final, stages (when ``trajectory``).
    """
    import time as _time

    from repro.core import distributed as D
    from repro.core import partition as _part

    ladder = tuple(ladder) if ladder is not None else default_ladder()
    t0 = _time.perf_counter()
    if pg is None:
        pg = _part.partition_graph(
            g, p, edge_balanced=edge_balanced, window_cap=window_cap,
            common_cap=common_cap, pad_to=pad_to,
        )
    n = pg.n_global
    frames: list = []
    path = [dict(cell="input", L=int(pg.L), E=int(pg.E))]
    descents = 0
    stages: list = []
    min_ratio = 1.0
    budget = cfg.max_rounds

    def _r_blk_for(cell) -> Optional[int]:
        if cfg.backend == "jnp":
            return None
        return cell.r_blk if (cell is not None and cell.r_blk) else cfg.r_blk

    def _build(pg_, cell=None, tag=None):
        return build_union_problem(
            pg_, cfg.backend, _r_blk_for(cell), plan_cache, plan_tag=tag,
        )

    prob = _build(pg)
    state = R.init_state(prob.w0, prob.is_local, prob.is_ghost)
    phase = "greedy" if algo == "greedy" else "reduce"

    if resume and ckpt is not None and ckpt.latest_step() is not None:
        man = ckpt.manifest()
        extra = man["extra"]
        tmpl = {
            "state": D.state_template(int(extra["union_v"][-1])),
            "frames": [D.state_template(int(v))
                       for v in extra["union_v"][:-1]],
        }
        tree = ckpt.restore(tmpl)
        by_name = {c.name: c for c in ladder}
        pg_k, prob_k = pg, prob
        for k, fs in enumerate(tree["frames"]):
            fs = R.RedState(*[jnp.asarray(x) for x in fs])
            frames.append(_Frame(state=fs, aux=prob_k.aux,
                                 is_local=prob_k.is_local))
            pg_k = _part.compact_partition(
                pg_k, np.asarray(fs.status), np.asarray(fs.w),
                pad_to=extra["dims"][k],
            )
            prob_k = _build(pg_k, by_name.get(extra["path"][k + 1]["cell"]),
                            tag="descent")
        pg, prob = pg_k, prob_k
        state = R.RedState(*[jnp.asarray(x) for x in tree["state"]])
        phase = extra["phase"]
        budget = int(extra["budget"])
        descents = int(extra["descents"])
        path = list(extra["path"])
        min_ratio = float(extra["min_ratio"])

    def _alive() -> int:
        status = np.asarray(state.status)
        return int(((status == UNDECIDED) & np.asarray(prob.is_local)).sum())

    def _save(cur_phase: str, cur_budget: int) -> None:
        if ckpt is None:
            return
        tree = {"state": state, "frames": [f.state for f in frames]}
        extra = dict(
            kind="solve_staged", phase=cur_phase, budget=int(cur_budget),
            descents=descents, path=path, min_ratio=min_ratio,
            union_v=[int(f.state.w.shape[0]) for f in frames]
                    + [int(state.w.shape[0])],
            dims=[{k: int(path[j + 1][k]) for k in ("L", "E")}
                  | dict(G=int(dmeta["G"]), B=int(dmeta["B"]),
                         S=int(dmeta["S"]))
                  for j, dmeta in enumerate(path[1:])],
        )
        ckpt.save(descents, tree, extra=extra)

    def _run_stage(phase_name: str, iters: int):
        nonlocal state
        took = {}
        with span("mwis.descent.stage", took, "ms"):
            state, rounds, flag = _stage_union_jit(
                state, prob.is_ghost, prob.aux, prob.halo, prob.plan,
                phase=phase_name, iters=int(iters), heavy_k=cfg.heavy_k,
                use_heavy=cfg.use_heavy, sweeps=cfg.sweeps_per_round,
                p=pg.p, schedule=cfg.schedule, backend=cfg.backend,
            )
            jax.block_until_ready(state.status)
        if trajectory:
            stages.append(dict(
                phase=phase_name, shape=path[-1]["cell"], L=int(pg.L),
                rounds=int(rounds), alive=_alive(),
                us=round(took["ms"] * 1e3, 1),
            ))
        return int(rounds), bool(flag)

    def _maybe_descend(cur_phase: str, cur_budget: int) -> None:
        nonlocal pg, prob, state, descents, min_ratio
        if not cfg.descent:
            return
        status = np.asarray(state.status)
        alive = int(((status == UNDECIDED)
                     & np.asarray(prob.is_local)).sum())
        if alive == 0:
            return
        min_ratio = min(min_ratio, alive / max(n, 1))
        need = D.kernel_shape(pg, status)
        cell = _pick_cell(ladder, need, pg.L, pg.E, cfg.descent_factor)
        if cell is None or not D.ghosts_consistent(pg, status):
            return
        frames.append(_Frame(state=state, aux=prob.aux,
                             is_local=prob.is_local))
        pg = _part.compact_partition(
            pg, status, np.asarray(state.w),
            pad_to=dict(L=cell.L, E=cell.E, G=cell.G, B=cell.B, S=cell.S),
        )
        prob = _build(pg, cell, tag="descent")
        state = R.init_state(prob.w0, prob.is_local, prob.is_ghost)
        descents += 1
        path.append(dict(cell=cell.name, L=int(pg.L), E=int(pg.E),
                         G=int(pg.G), B=int(pg.B), S=int(pg.S)))
        _save(cur_phase, cur_budget)
        if on_descent is not None:
            on_descent(descents, cell.name)

    def _reduce_phase(left: int) -> int:
        while left > 0:
            iters = min(cfg.descent_every, left) if cfg.descent else left
            rounds, changed = _run_stage("reduce", iters)
            left -= rounds
            _maybe_descend("reduce", left)
            if not changed:
                break
        return left

    def _greedy_phase() -> None:
        while _alive():
            iters = cfg.descent_every if cfg.descent else 100_000
            _, remaining = _run_stage("greedy", iters)
            _maybe_descend("greedy", 0)
            if not remaining:
                break

    if algo == "reduce":
        if phase == "reduce":
            budget = _reduce_phase(budget)
    elif algo == "greedy":
        _greedy_phase()
    elif algo == "rg":
        if phase == "reduce":
            budget = _reduce_phase(budget)
            phase = "greedy"
        _greedy_phase()
    elif algo == "rnp":
        while _alive():
            _reduce_phase(budget)
            budget = cfg.max_rounds
            if not _alive():
                break
            _run_stage("peel", 1)
    else:
        raise ValueError(f"unknown algo {algo!r}")

    # ---- stitch: reconstruct innermost-out through the frame stack ---- #
    def _members_at(state_, aux_, is_local_) -> np.ndarray:
        in_set = np.asarray(_reconstruct_jit(state_, aux_))
        members = np.zeros(n, dtype=bool)
        sel = in_set & np.asarray(is_local_)
        members[np.asarray(aux_.gid)[sel]] = True
        return members

    members = _members_at(state, prob.aux, prob.is_local)
    for fr in reversed(frames):
        status = np.asarray(fr.state.status).copy()
        gids = np.asarray(fr.aux.gid)
        member_of_gid = np.zeros(n + 1, dtype=bool)
        member_of_gid[:n] = members
        und = status == UNDECIDED
        decided_in = member_of_gid[np.where(gids >= 0, gids, n)] & und
        status[und] = EXCLUDED
        status[decided_in] = INCLUDED
        st2 = fr.state._replace(status=jnp.asarray(status.astype(np.int8)))
        members = _members_at(st2, fr.aux, fr.is_local)

    stats = dict(
        descents=descents, path=path, kernel_ratio=min_ratio,
        alive_final=_alive(), t_total=_time.perf_counter() - t0,
    )
    if trajectory:
        stats["stages"] = stages
    return members, stats


def solver_shard_map_fn(pg: PartitionedGraph, cfg: DisReduConfig, mesh,
                        algo: str, axis: str = "pe"):
    """Build the shard_map'd solver over stacked [p, ...] arrays."""
    from jax.sharding import PartitionSpec as P

    arrs = shard_map_arrays(pg, cfg)
    keys = list(arrs.keys())

    def per_pe(*args):
        aux, halo, plan, a = _unpack_per_pe(pg, keys, args)

        def exch(state):
            return X.exchange_shmap(
                state, aux, halo, axis=axis, method=cfg.exchange,
                backend=cfg.backend, plan=plan,
            )

        def gany(x):
            return jax.lax.psum(x.astype(jnp.int32), axis) > 0

        def peel(state, score):
            top = jnp.argmax(score)
            has = score[top] > I32_MIN
            idx = jnp.where(has, top, score.shape[0] - 1)
            status = state.status.at[idx].set(jnp.int8(EXCLUDED))
            return state._replace(status=status)

        ctx = Ctx(exchange=exch, gany=gany, peel=peel)
        state = R.init_state(a["w0"], a["is_local"], a["is_ghost"])
        state, iters = run_algorithm(state, aux, ctx, cfg, algo, plan=plan)
        members = R.reconstruct_members(state, aux)
        ex = lambda t: t.reshape((1,) + t.shape)
        return (ex(state.w), ex(state.status), ex(members),
                ex(state.offset), ex(state.log_n), ex(iters))

    in_specs = tuple(P(axis) for _ in keys)
    out_specs = (P(axis),) * 6
    fn = jax.shard_map(per_pe, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def run(arrays=None):
        if arrays is None:
            arrays = place_on_mesh(arrs, mesh, axis)
        return fn(*(arrays[k] for k in keys))

    return run, keys


def sweep_probe_shard_map_fn(pg: PartitionedGraph, cfg: DisReduConfig, mesh,
                             axis: str = "pe"):
    """Loop-free roofline probe: exactly ONE rule sweep + ONE halo exchange
    (+ one heavy-vertex pass).  DisRedu's while-loops have data-dependent
    trip counts, so the honest static roofline unit is per sweep-round —
    cost_analysis of this probe is exact (no hidden loop bodies)."""
    from jax.sharding import PartitionSpec as P

    arrs = shard_map_arrays(pg, cfg)
    keys = list(arrs.keys())

    def per_pe(*args):
        aux, halo, plan, a = _unpack_per_pe(pg, keys, args)
        state = R.init_state(a["w0"], a["is_local"], a["is_ghost"])
        state = E.sweep(
            state, aux, schedule=cfg.schedule, backend=cfg.backend, plan=plan
        )
        if cfg.use_heavy:
            state = R.rule_heavy_vertex(state, aux, cfg.heavy_k)
        state, _ = X.exchange_shmap(
            state, aux, halo, axis=axis, method=cfg.exchange,
            backend=cfg.backend, plan=plan,
        )
        ex = lambda t: t.reshape((1,) + t.shape)
        return ex(state.w), ex(state.status), ex(state.offset)

    fn = jax.shard_map(
        per_pe, mesh=mesh, in_specs=tuple(P(axis) for _ in keys),
        out_specs=(P(axis),) * 3, check_vma=False,
    )

    def run(arrays):
        return fn(*(arrays[k] for k in keys))

    return run, keys
