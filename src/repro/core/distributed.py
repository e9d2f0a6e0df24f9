"""DisReduS / DisReduA — the paper's distributed reduction algorithms (§5).

Round structure (Algorithm 5.1):

  while global reduction progress:
      LocalReduce(G_i)            — §5.1, vectorized rule sweeps to fixpoint
      ExchWeightUpdates + ExchStatusUpdates — one fused halo exchange
      (FilterMoves is a no-op here: the static-shape adaptation resolves the
       move cases via degree-one folds and Lemma 4.4 tie-breaking; DESIGN.md §2)

DisReduA (§5.4) is realised as *bounded staleness*: instead of waiting for
the local fixpoint, each PE exchanges after `stale_sweeps` rule sweeps.
That is the paper's asynchrony insight — don't serialize on quiescence;
trade message freshness against idle time — mapped onto SPMD collectives,
where XLA overlaps the independent interior sweeps with collective latency.

Two execution paths share all rule/exchange code:

  * union path   — all PEs stacked into one block-diagonal graph on one
    device (exact SPMD simulation; tests/benches on CPU),
  * shard_map path — PE axis = mesh devices, lax collectives (production,
    and the lowering target of the multi-pod dry-run).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as E
from repro.core import exchange as X
from repro.core import rules as R
from repro.core.local_reduce import local_reduce
from repro.core.partition import PartitionedGraph
from repro.core.spans import span

UNDECIDED, INCLUDED, EXCLUDED, FOLDED = 0, 1, 2, 3


@dataclasses.dataclass(frozen=True)
class DisReduConfig:
    heavy_k: int = 8
    use_heavy: bool = True
    mode: str = "sync"            # "sync" = DisReduS | "async" = DisReduA
    stale_sweeps: int = 2         # async: sweeps between exchanges
    exchange: str = "allgather"   # "allgather" | "a2a"  (shard_map path)
    schedule: str = "cheap"       # named rule schedule (engine.SCHEDULES)
    backend: str = "jnp"          # aggregate backend: jnp | blocked | pallas
    max_rounds: int = 10_000
    r_blk: Optional[int] = None   # blocked-ELL row-block height; None =
                                  # autotune at plan-build time (engine)
    # --- shape-descent policy (solvers.solve_staged) ------------------- #
    descent: bool = False         # re-pack the alive kernel onto smaller
                                  # ladder cells at stage boundaries
    descent_every: int = 2        # rounds (reduce/greedy) per stage between
                                  # descent checks
    descent_factor: int = 2       # hysteresis: only descend onto a cell
                                  # with cell.L * factor <= current L

    @property
    def sweeps_per_round(self) -> int:
        return 1_000_000 if self.mode == "sync" else self.stale_sweeps


class UnionProblem(NamedTuple):
    w0: jax.Array
    is_local: jax.Array
    is_ghost: jax.Array
    aux: R.Aux
    halo: X.Halo
    p: int
    V: int  # per-PE vertex count (union total = p * V)
    plan: Optional[E.SegPlan] = None  # blocked-ELL packing (non-jnp backends)


def pack_union_problem(
    pg: PartitionedGraph, backend: str = "jnp",
    r_blk: Optional[int] = None,
    plan_cache: Optional[E.PlanCache] = None,
    plan_tag: Optional[str] = None,
) -> UnionProblem:
    """Stack all PEs into one block-diagonal graph with offset indices, as
    host (numpy) arrays: :func:`upload_union_problem` places them.

    ``plan_cache`` (an :class:`repro.core.engine.PlanCache`) reuses the
    blocked-ELL SegPlan across calls whenever the union topology repeats —
    plan packing and window-payload construction are the dominant host cost
    for repeated instances, so callers that solve the same graph shape many
    times (the serving layer, round-robin benches) should share one cache.
    """
    p, V = pg.p, pg.V
    off_v = (np.arange(p, dtype=np.int64) * V)[:, None]

    def offset_idx(a: np.ndarray) -> np.ndarray:
        # per-PE local indices -> union indices (nil_i = i*V + nil)
        return (a.astype(np.int64) + off_v.reshape((p,) + (1,) * (a.ndim - 1))).astype(np.int32)

    row = offset_idx(pg.row).reshape(-1)
    col = offset_idx(pg.col).reshape(-1)
    window = offset_idx(pg.window).reshape(p * V, -1)
    edge_common = offset_idx(pg.edge_common).reshape(row.shape[0], -1)
    aux = R.Aux(
        row=row, col=col,
        gid=pg.gid.reshape(-1),
        is_local=pg.is_local.reshape(-1),
        is_iface=pg.is_iface.reshape(-1),
        owner_rank=pg.owner_pe.reshape(-1),
        window=window,
        win_complete=pg.win_complete.reshape(-1),
        win_adj_bits=pg.win_adj_bits.reshape(p * V, -1),
        edge_common=edge_common,
        # per-PE masks hold gids and flags, not slots: offsets leave them
        **{k: getattr(pg, k).reshape((-1,) + getattr(pg, k).shape[2:])
           for k in R.STATIC_MASKS},
    )
    plan = None
    if backend != "jnp":
        with span("mwis.reduce.plan"):
            plan = E.plan_for(
                plan_cache, row, p * V, r_blk=r_blk,
                col=col, gid=pg.gid.reshape(-1), window=window,
                win_adj_bits=pg.win_adj_bits.reshape(p * V, -1),
                tag=plan_tag,
            )
    return UnionProblem(
        w0=pg.w0.reshape(-1),
        is_local=pg.is_local.reshape(-1),
        is_ghost=pg.is_ghost.reshape(-1),
        aux=aux, halo=X.make_halo(pg, pe=None), p=p, V=V, plan=plan,
    )


def upload_union_problem(host: UnionProblem) -> UnionProblem:
    """Place a host-packed union problem's arrays on the device."""
    put = functools.partial(jax.tree.map, jnp.asarray)
    return host._replace(
        w0=jnp.asarray(host.w0), is_local=jnp.asarray(host.is_local),
        is_ghost=jnp.asarray(host.is_ghost), aux=put(host.aux),
        halo=put(host.halo), plan=put(host.plan),
    )


def build_union_problem(
    pg: PartitionedGraph, backend: str = "jnp",
    r_blk: Optional[int] = None,
    plan_cache: Optional[E.PlanCache] = None,
    plan_tag: Optional[str] = None,
) -> UnionProblem:
    """:func:`pack_union_problem` on the host, then
    :func:`upload_union_problem`: the union problem on the device."""
    with span("mwis.reduce.pack"):
        host = pack_union_problem(pg, backend, r_blk, plan_cache, plan_tag)
        with span("mwis.reduce.upload"):
            return upload_union_problem(host)


# --------------------------------------------------------------------- #
# union path (single-device SPMD simulation)
# --------------------------------------------------------------------- #
def _round_union(state, prob: UnionProblem, cfg: DisReduConfig):
    state = local_reduce(
        state, prob.aux, heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
        max_sweeps=cfg.sweeps_per_round, schedule=cfg.schedule,
        backend=cfg.backend, plan=prob.plan,
    )
    state, _ = X.exchange_union(
        state, prob.aux, prob.halo, p=prob.p,
        backend=cfg.backend, plan=prob.plan,
    )
    return state


@functools.partial(
    jax.jit,
    static_argnames=("heavy_k", "use_heavy", "sweeps", "max_rounds", "p",
                     "schedule", "backend"),
)
def _disredu_union_jit(
    w0, is_local, is_ghost, aux, halo, plan, *, heavy_k, use_heavy, sweeps,
    max_rounds, p, schedule="cheap", backend="jnp"
):
    prob = UnionProblem(w0, is_local, is_ghost, aux, halo, p, 0, plan)
    cfg = DisReduConfig(
        heavy_k=heavy_k, use_heavy=use_heavy,
        mode="sync" if sweeps >= 1_000_000 else "async",
        stale_sweeps=sweeps, max_rounds=max_rounds, schedule=schedule,
        backend=backend,
    )
    state0 = R.init_state(w0, is_local, is_ghost)

    def body(carry):
        state, rounds, _ = carry
        snap_s, snap_w = state.status, state.w
        state = _round_union(state, prob, cfg)
        with jax.named_scope("mwis.round.vote"):
            changed = ((state.status != snap_s).any()
                       | (state.w != snap_w).any())
        return state, rounds + 1, changed

    def cond(carry):
        _, rounds, changed = carry
        return changed & (rounds < max_rounds)

    state, rounds, _ = jax.lax.while_loop(
        cond, body, (state0, jnp.zeros((), jnp.int32), jnp.ones((), bool))
    )
    return state, rounds


def disredu(
    pg: PartitionedGraph, cfg: DisReduConfig = DisReduConfig()
) -> Tuple[R.RedState, UnionProblem, int]:
    """Run DisReduS/DisReduA on the union simulation path."""
    prob = build_union_problem(pg, cfg.backend, cfg.r_blk)
    state, rounds = _disredu_union_jit(
        prob.w0, prob.is_local, prob.is_ghost, prob.aux, prob.halo,
        prob.plan,
        heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
        sweeps=cfg.sweeps_per_round, max_rounds=cfg.max_rounds, p=prob.p,
        schedule=cfg.schedule, backend=cfg.backend,
    )
    return state, prob, int(rounds)


# --------------------------------------------------------------------- #
# shard_map path (production; also the dry-run lowering target)
# --------------------------------------------------------------------- #
def shard_map_arrays(pg: PartitionedGraph, cfg: DisReduConfig):
    """The stacked [p, ...] host arrays a shard_map driver consumes — the
    partitioned graph plus, for non-jnp backends, the per-PE blocked-ELL
    plan (packed host-side with a shared E_BLK so it meshes-shards)."""
    arrs = dict(pg.device_arrays())
    if cfg.backend != "jnp":
        if pg.row is None:
            raise ValueError(
                "backend=%r needs concrete edge arrays to pack the "
                "blocked-ELL plan; abstract (dry-run) graphs must use the "
                "jnp backend" % (cfg.backend,)
            )
        plan = E.build_plan_stacked(
            pg.row, pg.V, r_blk=cfg.r_blk,
            cols=pg.col, gids=pg.gid, windows=pg.window,
            win_adj_bits=pg.win_adj_bits,
        )
        arrs["plan_perm"] = np.asarray(plan.edge_perm)
        arrs["plan_lrow"] = np.asarray(plan.lrow)
        arrs["plan_wbits"] = np.asarray(plan.wbits)
        arrs["plan_wnh"] = np.asarray(plan.wnh)
        arrs["plan_rblk"] = np.zeros(
            (pg.p, plan.r_blk, 0), dtype=np.int32
        )
    return arrs


def place_on_mesh(arrs: dict, mesh, axis: str = "pe") -> dict:
    """Put each stacked [p, ...] host array straight onto its PE's device
    (``NamedSharding(mesh, P(axis))``), never staging it on device 0."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return {k: jax.device_put(np.asarray(v), sharding)
            for k, v in arrs.items()}


def _unpack_per_pe(pg: PartitionedGraph, keys, args):
    """Squeeze the leading PE axis and rebuild (aux, halo, plan, a)."""
    a = dict(zip(keys, [x.reshape(x.shape[1:]) for x in args]))
    aux = R.Aux(
        row=a["row"], col=a["col"], gid=a["gid"], is_local=a["is_local"],
        is_iface=a["is_iface"], owner_rank=a["owner_pe"],
        window=a["window"], win_complete=a["win_complete"],
        win_adj_bits=a["win_adj_bits"], edge_common=a["edge_common"],
        # built once per call, before the program's loops
        **R.static_masks(a["row"], a["col"], a["gid"], a["is_local"],
                         a["win_complete"], a["window"], a["edge_common"]),
    )
    L, G = pg.L, pg.G
    halo = X.Halo(
        iface_slots=a["iface_slots"],
        ghost_vertex=L + jnp.arange(G, dtype=jnp.int32),
        ghost_owner_pe=jnp.maximum(a["owner_pe"][L : L + G], 0),
        ghost_owner_slot=a["ghost_owner_slot"],
        ghost_valid=a["is_ghost"][L : L + G],
        send_slot=a["send_slot"], recv_ghost=a["recv_ghost"],
    )
    plan = (
        E.SegPlan(
            edge_perm=a["plan_perm"], lrow=a["plan_lrow"],
            rblk_tpl=a["plan_rblk"], wbits=a["plan_wbits"],
            wnh=a["plan_wnh"],
        )
        if "plan_perm" in a else None
    )
    return aux, halo, plan, a


def disredu_shard_map_fn(pg: PartitionedGraph, cfg: DisReduConfig, mesh,
                         axis: str = "pe"):
    """Return a jit-able function over stacked [p, ...] arrays running the
    full DisRedu round loop under shard_map on `mesh` (axis name `axis`)."""
    from jax.sharding import PartitionSpec as P

    arrs = shard_map_arrays(pg, cfg)
    keys = list(arrs.keys())

    def per_pe(*args):
        aux, halo, plan, a = _unpack_per_pe(pg, keys, args)
        state0 = R.init_state(a["w0"], a["is_local"], a["is_ghost"])

        def body(carry):
            state, rounds, _ = carry
            snap_s, snap_w = state.status, state.w
            state = local_reduce(
                state, aux, heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy,
                max_sweeps=cfg.sweeps_per_round, schedule=cfg.schedule,
                backend=cfg.backend, plan=plan,
            )
            state, _ = X.exchange_shmap(
                state, aux, halo, axis=axis, method=cfg.exchange,
                backend=cfg.backend, plan=plan,
            )
            with jax.named_scope("mwis.round.vote"):
                local_changed = (
                    (state.status != snap_s).any() | (state.w != snap_w).any()
                )
                changed = jax.lax.psum(
                    local_changed.astype(jnp.int32), axis) > 0
            return state, rounds + 1, changed

        def cond(carry):
            _, rounds, changed = carry
            return changed & (rounds < cfg.max_rounds)

        state, rounds, _ = jax.lax.while_loop(
            cond, body,
            (state0, jnp.zeros((), jnp.int32), jnp.ones((), bool)),
        )
        ex = lambda a: a.reshape((1,) + a.shape)
        return ex(state.w), ex(state.status), ex(state.log_kind), \
            ex(state.log_v), ex(state.log_u), ex(state.log_n), \
            ex(state.offset), ex(rounds)

    in_specs = tuple(P(axis) for _ in keys)
    out_specs = (P(axis),) * 8
    fn = jax.shard_map(per_pe, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def run(arrays=None):
        if arrays is None:
            arrays = place_on_mesh(arrs, mesh, axis)
        return fn(*(arrays[k] for k in keys))

    return run, keys


# --------------------------------------------------------------------- #
# result extraction
# --------------------------------------------------------------------- #
def kernel_stats(
    pg: PartitionedGraph, state: R.RedState
) -> Tuple[int, int]:
    """(#alive vertices, #alive undirected edges) of the reduced graph."""
    status = np.asarray(state.status)
    is_local = np.asarray(pg.is_local.reshape(-1))
    alive_v = int(((status == UNDECIDED) & is_local).sum())
    row = np.asarray(pg.row).astype(np.int64)
    col = np.asarray(pg.col).astype(np.int64)
    off = (np.arange(pg.p, dtype=np.int64) * pg.V)[:, None]
    ur, uc = (row + off).reshape(-1), (col + off).reshape(-1)
    ea = (status[ur] == UNDECIDED) & (status[uc] == UNDECIDED)
    loc = np.asarray(pg.is_local.reshape(-1))
    # count each undirected edge once: local rows only, and only (u < v) by gid
    gids = np.asarray(pg.gid.reshape(-1))
    cnt = int((ea & loc[ur] & (gids[ur] < gids[uc])).sum())
    return alive_v, cnt


def kernel_shape(pg: PartitionedGraph, status: np.ndarray) -> dict:
    """Exact per-PE padded-size requirements of the alive kernel.

    Returns the smallest L/G/E/B/S a :func:`partition.compact_partition`
    restriction of ``pg`` at this state needs (maxima over PEs, before any
    ladder-cell flooring).  This is the stage-boundary measurement the
    shape-descent policy compares against the static cell ladder.
    """
    p, V, L, G = pg.p, pg.V, pg.L, pg.G
    status = np.asarray(status).reshape(p, V)
    alive = status == UNDECIDED
    keep_l = pg.is_local & alive
    keep_g = pg.is_ghost & alive
    keep = keep_l | keep_g
    nl = ng = ne = nb = ns = 0
    for i in range(p):
        nl = max(nl, int(keep_l[i].sum()))
        ng = max(ng, int(keep_g[i].sum()))
        ne = max(ne, int((keep[i][pg.row[i]] & keep[i][pg.col[i]]).sum()))
        nb = max(nb, int((keep_l[i] & pg.is_iface[i]).sum()))
        gk = np.flatnonzero(keep_g[i])
        if gk.size:
            owners = pg.owner_pe[i, gk]
            ns = max(ns, int(np.bincount(owners[owners >= 0]).max()))
    return dict(L=nl, G=ng, E=ne, B=nb, S=ns)


def ghosts_consistent(pg: PartitionedGraph, status: np.ndarray) -> bool:
    """True iff every valid ghost slot is alive exactly when its owner's
    local copy is alive — the exchange-consistency precondition of
    :func:`partition.compact_partition`.  Holds at every post-exchange
    round boundary; transiently false between a peel and the next
    exchange (the staged solver never descends there)."""
    p, V = pg.p, pg.V
    status = np.asarray(status).reshape(p, V)
    alive = status == UNDECIDED
    owner_alive = np.zeros(pg.n_global, dtype=bool)
    for i in range(p):
        loc = pg.is_local[i]
        owner_alive[pg.gid[i][loc]] = alive[i][loc]
    for i in range(p):
        gh = pg.is_ghost[i]
        if (alive[i][gh] != owner_alive[pg.gid[i][gh]]).any():
            return False
    return True


def state_template(union_v: int) -> R.RedState:
    """A zero :class:`RedState` with the union-layout shapes for ``p*V =
    union_v`` slots — the restore template for checkpointed stage states
    (shape-descent checkpoints store one state per descent level, each at
    its own ladder shape; the level's V is recorded in the checkpoint
    manifest)."""
    z = jnp.zeros(union_v, jnp.int32)
    return R.init_state(z, jnp.zeros(union_v, bool), jnp.zeros(union_v, bool))


def members_global(
    pg: PartitionedGraph, state: R.RedState, aux: R.Aux
) -> np.ndarray:
    """Reconstruct and assemble the global member mask (union layout)."""
    in_set = np.asarray(R.reconstruct_members(state, aux))
    members = np.zeros(pg.n_global, dtype=bool)
    is_local = np.asarray(pg.is_local.reshape(-1))
    gids = np.asarray(pg.gid.reshape(-1))
    sel = in_set & is_local
    members[gids[sel]] = True
    return members
