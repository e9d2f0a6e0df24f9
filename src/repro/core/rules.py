"""Vectorized masked reduction rules — the paper's §4.3 in JAX array form.

Every rule is evaluated for *all* vertices of a PE's local subgraph at once
(segment reductions over the edge list + static capped neighbor windows),
instead of the per-vertex worklist of a sequential CPU reducer.  This is the
TPU-native re-expression of the paper's observation that the rules "act very
locally": locality means each test is a bounded neighborhood aggregate, i.e.
exactly a masked segment op.

Batching soundness.  A sequential reducer applies one rule at a time; a
vectorized sweep fires many applications simultaneously, which is unsound
without care (two adjacent vertices both passing an include test must not
both be included; two vertices excluding each other via symmetric
single-edge certificates would lose the optimum).  We restore soundness
with deterministic priority filters (global vertex id = the paper's
PE-rank/ID tie-breaking generalised to every rule):

  * include rules   — candidates are accepted only if they beat every
    candidate neighbor (accepted set is independent; include rules are
    monotone under deletion of other accepted vertices, so a batch equals
    some sequential order).
  * exclude rules   — a vertex is excluded only if its certificate vertex
    has *higher* priority; certificate chains therefore strictly ascend and
    the standard rerouting argument (any solution using an excluded vertex
    can be rerouted toward higher-priority certificates) terminates.
  * weight transfer — accepted folds must be the unique candidate within
    two hops, so their closed neighborhoods are disjoint and the batched
    weight decrements cannot race.

Ghost semantics follow the distributed reduction model (Def. 4.1):
ghost weights are upper bounds (Lemma 4.2), neighborhoods are supersets
(Lemma 4.3); every test below is monotone in the right direction so stale
border data only ever makes a rule *more conservative*, never unsound.
Interface-vertex includes are proposals (Remark 4.6); conflict resolution
happens in the exchange step (:mod:`repro.core.distributed`).

Static-factor contract: every factor of a rule test that depends on the
graph alone (endpoint locality, gid order, the extended single-edge target
filter, ...) is computed once per graph by :func:`static_masks` and carried
in :class:`Aux`; inside the sweep loop rules gather only state.

Aggregate-declaration contract (see ARCHITECTURE.md): every rule *declares*
the neighborhood aggregates its TEST needs via ``@_requires(...)`` and
receives them in a :class:`SweepCtx` — rules never issue their own segment
reductions for tests.  The aggregate engine (:mod:`repro.core.engine`)
computes the union of the scheduled rules' requirements and dispatches the
segment reductions through a pluggable backend (jnp or the Pallas
blocked-ELL kernels).  Rule *applications* (scatters, certificate activity)
always read fresh status — those stay inline here.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ops import segment_max, segment_sum

from repro.kernels.wedge_intersect.ops import window_active_bits


def _requires(*aggs: str):
    """Declare which SweepCtx aggregates a rule's test consumes."""
    unknown = set(aggs) - set(SweepCtx._fields)
    if unknown:
        raise ValueError(
            f"unknown aggregate(s) {sorted(unknown)}; "
            f"SweepCtx fields are {SweepCtx._fields}"
        )

    def deco(fn):
        fn.requires = frozenset(aggs)
        return fn

    return deco

UNDECIDED, INCLUDED, EXCLUDED, FOLDED = 0, 1, 2, 3
LOG_FOLD1, LOG_WT = 1, 2

I32_MIN = jnp.iinfo(jnp.int32).min


class Aux(NamedTuple):
    """Static (per-PE) graph structure; never modified by reductions.

    The last six fields are the rule tests' static factors, functions of
    the fields above them alone (:func:`static_masks` defines them); they
    are built once per graph so that no sweep gathers static data again.
    """

    row: jax.Array            # [E] i32 source local idx (pad = nil)
    col: jax.Array            # [E] i32 target local idx (pad = nil)
    gid: jax.Array            # [V] i32 global id (nil/pad = -1)
    is_local: jax.Array       # [V] bool
    is_iface: jax.Array       # [V] bool
    owner_rank: jax.Array     # [V] i32 owning PE (tie-breaking, Lemma 4.5)
    window: jax.Array         # [V, D] i32 capped neighbor lists (pad = nil)
    win_complete: jax.Array   # [V] bool
    win_adj_bits: jax.Array   # [V, D] i32 static pairwise adjacency bits
    edge_common: jax.Array    # [E, Dc] i32 capped common neighborhoods
    e_local: jax.Array        # [E] bool  is_local[row] & is_local[col]
    e_up: jax.Array           # [E] bool  gid[row] > gid[col]
    ec_ok: jax.Array          # [E, Dc] bool  extended single-edge targets
    gid_col: jax.Array        # [E] i32  gid[col]
    wc_col: jax.Array         # [E] bool  win_complete[col]
    win_real: jax.Array       # [V, D] bool  gid[window] >= 0


#: The :class:`Aux` fields :func:`static_masks` builds.
STATIC_MASKS = ("e_local", "e_up", "ec_ok", "gid_col", "wc_col", "win_real")


def static_masks(row, col, gid, is_local, win_complete, window,
                 edge_common) -> dict:
    """The rule tests' static factors of one graph, keyed by Aux field.

    Works on numpy (host packing) and jax arrays (inside a traced
    program) alike; index arrays address one PE's (or one union's) slots.
    ``ec_ok`` is extended single-edge's whole static target filter: a
    common neighbor is a real local vertex below both endpoints in gid.
    """
    xp = np if isinstance(row, np.ndarray) else jnp
    g_row, g_col = gid[row], gid[col]
    g_ec = gid[edge_common]
    low = xp.minimum(g_row, g_col)
    return dict(
        e_local=is_local[row] & is_local[col],
        e_up=g_row > g_col,
        ec_ok=is_local[edge_common] & (g_ec >= 0) & (g_ec < low[:, None]),
        gid_col=g_col,
        wc_col=win_complete[col],
        win_real=gid[window] >= 0,
    )


class RedState(NamedTuple):
    """Mutable reduction state (one PE)."""

    w: jax.Array        # [V] i32 current weights
    status: jax.Array   # [V] i8
    log_kind: jax.Array  # [LOG] i8   (fold log for reconstruction)
    log_v: jax.Array    # [LOG] i32
    log_u: jax.Array    # [LOG] i32
    log_n: jax.Array    # [] i32
    offset: jax.Array   # [] i32  (weight reclaimed by folds; reporting)
    changed: jax.Array  # [] bool (any rule fired in the current sweep)


def init_state(w0: jax.Array, is_local: jax.Array, is_ghost: jax.Array) -> RedState:
    V = w0.shape[0]
    L = int(is_local.shape[0])
    status = jnp.where(is_local | is_ghost, UNDECIDED, EXCLUDED).astype(jnp.int8)
    log_cap = V + 1  # each fold retires one vertex forever => never overflows
    return RedState(
        w=w0.astype(jnp.int32),
        status=status,
        log_kind=jnp.zeros(log_cap, jnp.int8),
        log_v=jnp.zeros(log_cap, jnp.int32),
        log_u=jnp.zeros(log_cap, jnp.int32),
        log_n=jnp.zeros((), jnp.int32),
        offset=jnp.zeros((), jnp.int32),
        changed=jnp.zeros((), bool),
    )


# --------------------------------------------------------------------- #
# shared masked aggregates
# --------------------------------------------------------------------- #
def _active(state: RedState) -> jax.Array:
    return state.status == UNDECIDED


def _edge_active(aux: Aux, active: jax.Array) -> jax.Array:
    return active[aux.row] & active[aux.col]


def _aw(state: RedState, active: jax.Array) -> jax.Array:
    return jnp.where(active, state.w, 0)


def _act_deg(aux: Aux, eact: jax.Array, V: int) -> jax.Array:
    return segment_sum(eact.astype(jnp.int32), aux.row, num_segments=V)


def _accept_independent(
    aux: Aux, eact: jax.Array, cand: jax.Array, V: int
) -> jax.Array:
    """Filter include candidates to an independent set (gid priority)."""
    nbr_cand_gid = jnp.where(eact & cand[aux.col], aux.gid_col, -1)
    m = segment_max(nbr_cand_gid, aux.row, num_segments=V)
    m = jnp.maximum(m, -1)
    return cand & (aux.gid > m)


def _apply_include(
    state: RedState, aux: Aux, eact: jax.Array, accept: jax.Array
) -> RedState:
    status = jnp.where(accept, jnp.int8(INCLUDED), state.status)
    hit = segment_max(
        (accept[aux.row] & eact).astype(jnp.int32), aux.col,
        num_segments=state.w.shape[0],
    ) > 0
    status = jnp.where(hit & (status == UNDECIDED), jnp.int8(EXCLUDED), status)
    return state._replace(status=status, changed=state.changed | accept.any())


def _log_append(
    state: RedState, mask: jax.Array, kind: int, v_idx: jax.Array,
    u_idx: jax.Array
) -> RedState:
    cap = state.log_kind.shape[0]
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    pos = jnp.where(mask, state.log_n + rank, cap - 1)
    # cap-1 slot is a scratch sentinel; log_n never reaches it (see init_state)
    log_kind = state.log_kind.at[pos].set(jnp.where(mask, jnp.int8(kind), 0))
    log_v = state.log_v.at[pos].set(jnp.where(mask, v_idx, 0))
    log_u = state.log_u.at[pos].set(jnp.where(mask, u_idx, 0))
    n = state.log_n + mask.sum(dtype=jnp.int32)
    return state._replace(log_kind=log_kind, log_v=log_v, log_u=log_u, log_n=n)


class SweepCtx(NamedTuple):
    """Rule-test aggregates, produced by the engine's pluggable backend.

    The engine (:mod:`repro.core.engine`) fills exactly the fields the
    scheduled rules declared via ``@_requires`` — undeclared fields are
    ``None``, so a rule reading past its declaration fails loudly.

    Staleness soundness (EXPERIMENTS.md §Perf H3): when the schedule
    snapshots aggregates once per sweep, adjacency is static and
    weights/activity only decrease, so snapshot aggregates are upper bounds
    of their fresh values — every rule test is monotone in the safe
    direction.  Rule *applications* and certificate activity always use
    fresh status (recomputed eact), so cross-family conflicts inside one
    sweep cannot arise."""

    S: Optional[jax.Array]         # [V] neighborhood weight sums
    deg: Optional[jax.Array]       # [V] active degrees
    M: Optional[jax.Array]         # [V] max neighbor weight
    only: Optional[jax.Array]      # [V] the unique active neighbor (deg-1)
    act_bits: Optional[jax.Array]  # [V] window active bits
    clique: Optional[jax.Array]    # [V] active window forms a clique


# --------------------------------------------------------------------- #
# rule: degree zero / one  (Meta rule + Remark 4.8, fold form of Gu et al.)
# --------------------------------------------------------------------- #
@_requires("deg", "only")
def rule_degree_one(state: RedState, aux: Aux, ctx: SweepCtx) -> RedState:
    V = state.w.shape[0]
    active = _active(state)
    eact = _edge_active(aux, active)
    deg, only = ctx.deg, ctx.only
    w_u = state.w[only]

    # (a) isolated vertices
    acc0 = aux.is_local & active & (deg == 0)
    state = _apply_include(state, aux, eact, acc0)

    # (b) degree-one include: w(v) >= w_i(u)  — upper bound is enough
    #     (ghost case: propose per Remark 4.6)
    active = _active(state)
    eact = _edge_active(aux, active)
    cand = aux.is_local & active & (deg == 1) & (state.w >= w_u)
    acc1 = _accept_independent(aux, eact, cand, V)
    state = _apply_include(state, aux, eact, acc1)

    # (c) degree-one fold: w(v) < w(u), u local:
    #       w(u) -= w(v);  v FOLDED;  v ∈ I  iff  u ∉ I.
    active = _active(state)
    cand = aux.is_local & active & (deg == 1) & (state.w < w_u)
    cand &= (aux.is_local & active)[only]
    # one fold per target u per sweep: keep the max-gid candidate
    tgt = jnp.where(cand, only, V - 1)
    best = jnp.full(V, -1, jnp.int32).at[tgt].max(jnp.where(cand, aux.gid, -1))
    acc = cand & (aux.gid == best[only])
    w = state.w.at[jnp.where(acc, only, V - 1)].add(
        jnp.where(acc, -state.w, 0)
    )
    w = w.at[V - 1].set(0)
    status = jnp.where(acc, jnp.int8(FOLDED), state.status)
    offset = state.offset + jnp.where(acc, state.w, 0).sum(dtype=jnp.int32)
    state = state._replace(
        w=w, status=status, offset=offset, changed=state.changed | acc.any()
    )
    idx = jnp.arange(V, dtype=jnp.int32)
    return _log_append(state, acc, LOG_FOLD1, idx, only.astype(jnp.int32))


# --------------------------------------------------------------------- #
# rule: Dist. Neighborhood Removal (Reduction 4.3)
# --------------------------------------------------------------------- #
@_requires("S")
def rule_neighborhood_removal(state: RedState, aux: Aux,
                              ctx: SweepCtx) -> RedState:
    V = state.w.shape[0]
    active = _active(state)
    eact = _edge_active(aux, active)
    s = ctx.S
    cand = aux.is_local & active & (state.w >= s)
    acc = _accept_independent(aux, eact, cand, V)
    return _apply_include(state, aux, eact, acc)


# --------------------------------------------------------------------- #
# rule: Distributed Simplicial Vertex (Reduction 4.4)
# --------------------------------------------------------------------- #
@_requires("clique", "M")
def rule_simplicial(state: RedState, aux: Aux, ctx: SweepCtx) -> RedState:
    V = state.w.shape[0]
    active = _active(state)
    eact = _edge_active(aux, active)
    clique, m = ctx.clique, ctx.M
    cand = (
        aux.is_local & active & aux.win_complete & clique & (state.w >= m)
    )
    acc = _accept_independent(aux, eact, cand, V)
    return _apply_include(state, aux, eact, acc)


# --------------------------------------------------------------------- #
# rule: Dist. Simplicial Weight Transfer (Reduction 4.5)
# --------------------------------------------------------------------- #
@_requires("clique", "M", "deg")
def rule_weight_transfer(state: RedState, aux: Aux,
                         ctx: SweepCtx) -> RedState:
    V = state.w.shape[0]
    D = aux.window.shape[1]
    active = _active(state)
    eact = _edge_active(aux, active)
    clique, m, deg = ctx.clique, ctx.M, ctx.deg

    # v must be max-weight among the simplicial vertices of N(v).  A neighbor
    # whose simpliciality we cannot decide (incomplete window) blocks v.
    simpl_known = aux.win_complete & clique
    nbr_blocks = eact & (state.w[aux.col] > state.w[aux.row]) & (
        simpl_known[aux.col] | ~aux.wc_col
    )
    blocked = segment_max(
        nbr_blocks.astype(jnp.int32), aux.row, num_segments=V
    ) > 0

    cand = (
        aux.is_local & active & ~aux.is_iface & simpl_known
        & (state.w < m) & ~blocked & (deg >= 1)
    )
    # unique within two hops (gid priority) => disjoint closed neighborhoods
    m1 = segment_max(
        jnp.where(eact & cand[aux.col], aux.gid_col, -1), aux.row,
        num_segments=V,
    )
    m1 = jnp.maximum(m1, -1)
    m2 = segment_max(jnp.where(eact, m1[aux.col], -1), aux.row, num_segments=V)
    m2 = jnp.maximum(m2, -1)
    acc = cand & (aux.gid > m1) & (aux.gid >= m2)

    # apply the fold: remove X = {u in N[v]: w(u) <= w(v)}, transfer weight.
    # entry activity here must be FRESH (application, not test): recompute
    # from current status via the vectorized window helper, not from ctx
    fresh_bits = window_active_bits(_active(state), aux.win_real, aux.window)
    wv = state.w
    tgt = aux.window  # [V, D]
    ent_active = ((fresh_bits[:, None] >> jnp.arange(D)[None, :]) & 1) == 1
    accb = acc[:, None]
    excl_upd = accb & ent_active & (state.w[tgt] <= wv[:, None])
    dec_upd = accb & ent_active & (state.w[tgt] > wv[:, None])
    nil_slot = V - 1
    # plain EXCLUDED fill: non-accepted slots scatter onto the nil slot,
    # which is EXCLUDED by invariant, so the unconditional value is safe
    status = state.status.at[jnp.where(excl_upd, tgt, nil_slot)].set(
        jnp.int8(EXCLUDED)
    )
    status = jnp.where(acc, jnp.int8(FOLDED), status)
    w = state.w.at[jnp.where(dec_upd, tgt, nil_slot)].add(
        jnp.where(dec_upd, -wv[:, None], 0)
    )
    w = w.at[nil_slot].set(0)
    offset = state.offset + jnp.where(acc, wv, 0).sum(dtype=jnp.int32)
    state = state._replace(
        w=w, status=status, offset=offset, changed=state.changed | acc.any()
    )
    idx = jnp.arange(V, dtype=jnp.int32)
    return _log_append(state, acc, LOG_WT, idx, idx)


# --------------------------------------------------------------------- #
# rule: Distributed Basic Single-Edge (Reduction 4.6)
# --------------------------------------------------------------------- #
@_requires("S")
def rule_basic_single_edge(state: RedState, aux: Aux,
                           ctx: SweepCtx) -> RedState:
    V = state.w.shape[0]
    active = _active(state)
    eact = _edge_active(aux, active)
    aw = _aw(state, active)
    s = ctx.S
    # capped common-neighborhood weight (lower bound => conservative)
    c = aw[aux.edge_common].sum(axis=1)  # aw is 0 off the active set
    val = s[aux.row] - c  # >= true ω(N(u) \ N(v)) which contains v itself
    test = (
        eact
        & aux.e_local
        & (val <= state.w[aux.row])
        & aux.e_up  # ascending certificate chain
    )
    excl = segment_max(test.astype(jnp.int32), aux.col, num_segments=V) > 0
    status = jnp.where(
        excl & active & aux.is_local, jnp.int8(EXCLUDED), state.status
    )
    fired = (excl & active & aux.is_local).any()
    return state._replace(status=status, changed=state.changed | fired)


# --------------------------------------------------------------------- #
# rule: Dist. Extended Single-Edge (Reduction 4.7)
# --------------------------------------------------------------------- #
@_requires("S")
def rule_extended_single_edge(state: RedState, aux: Aux,
                              ctx: SweepCtx) -> RedState:
    V = state.w.shape[0]
    active = _active(state)
    eact = _edge_active(aux, active)
    aw = _aw(state, active)
    s = ctx.S
    # edge e = (v=row, u=col):  w(v) >= S(v) - aw(u)  => exclude common nbrs
    test = (
        eact
        & aux.e_local
        & (s[aux.row] - aw[aux.col] <= state.w[aux.row])
    )
    tgt = aux.edge_common  # [E, Dc]
    upd = test[:, None] & active[tgt] & aux.ec_ok
    nil_slot = V - 1
    status = state.status.at[jnp.where(upd, tgt, nil_slot)].set(jnp.int8(EXCLUDED))
    fired = upd.any()
    return state._replace(status=status, changed=state.changed | fired)


# --------------------------------------------------------------------- #
# rule: Distributed Heavy Vertex (Reduction 4.2) — exact sub-MWIS
# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("heavy_k",))
def _alpha_neighborhood(
    w: jax.Array, status: jax.Array, aux: Aux, heavy_k: int
) -> jax.Array:
    """[V] i32 — exact α(G_i[N_i(v)]) for active windows with ≤K active
    entries; 2^K subset enumeration against static adjacency bitmasks (the
    KaMIS-wB&R stand-in, vectorised for the VPU/MXU)."""
    V, D = aux.window.shape
    K = heavy_k
    active = status == UNDECIDED
    ent_ok = active[aux.window] & aux.win_real  # [V, D]
    # stable-sort entries: active first, keep the first K
    order = jnp.argsort(~ent_ok, axis=1, stable=True)[:, :K]  # [V, K]
    ent = jnp.take_along_axis(aux.window, order, axis=1)      # [V, K]
    ent_act = jnp.take_along_axis(ent_ok, order, axis=1)      # [V, K]
    wk = jnp.where(ent_act, w[ent], 0).astype(jnp.int32)      # [V, K]
    # permuted adjacency bits: bit j of row i = adjacency(order_i, order_j)
    bits_full = jnp.take_along_axis(aux.win_adj_bits, order, axis=1)  # [V, K]
    adj = jnp.zeros((V, K), jnp.int32)
    for j in range(K):
        oj = order[:, j]
        bit_j = (bits_full >> oj[:, None]) & 1  # [V, K] adjacency to entry j
        adj |= bit_j << j
    subsets = jnp.arange(1 << K, dtype=jnp.int32)               # [T]
    sel = ((subsets[:, None] >> jnp.arange(K)[None, :]) & 1)     # [T, K]
    totals = wk @ sel.T.astype(jnp.int32)                        # [V, T]
    conflict = jnp.zeros(totals.shape, bool)
    for i in range(K):
        in_sub = sel[:, i] == 1                                  # [T]
        hits = (subsets[None, :] & adj[:, i : i + 1]) != 0       # [V, T]
        conflict |= in_sub[None, :] & hits
    alpha = jnp.where(conflict, -1, totals).max(axis=1)
    return jnp.maximum(alpha, 0)


def rule_heavy_vertex(state: RedState, aux: Aux, heavy_k: int = 8) -> RedState:
    V = state.w.shape[0]
    active = _active(state)
    eact = _edge_active(aux, active)
    deg = _act_deg(aux, eact, V)
    alpha = _alpha_neighborhood(state.w, state.status, aux, heavy_k)
    cand = (
        aux.is_local & active & aux.win_complete
        & (deg <= heavy_k) & (state.w >= alpha)
    )
    acc = _accept_independent(aux, eact, cand, V)
    return _apply_include(state, aux, eact, acc)


def reconstruct_members(state: RedState, aux: Aux) -> jax.Array:
    """Replay the fold log in reverse; returns [V] bool membership.

    INCLUDED statuses seed the set; FOLD1 (v ∈ I ⟺ u ∉ I) and WT
    (v ∈ I ⟺ I ∩ N(v) = ∅, window-complete by rule gating) records replay
    newest-first.  All record targets are local by rule construction.

    The body masks iterations ≥ log_n onto the nil slot: under vmap (the
    batched serving path) the lowered while_loop runs every batch element
    for the max trip count, and an unguarded body would re-apply a clamped
    log record to a real vertex.  Writing False to the nil slot is inert —
    nil is never local, so it is never reported as a member.
    """
    in_set = state.status == INCLUDED
    nil = state.status.shape[0] - 1

    def body(i, in_set):
        live = i < state.log_n
        k = jnp.maximum(state.log_n - 1 - i, 0)
        kind = state.log_kind[k]
        v = state.log_v[k]
        u = state.log_u[k]
        fold1_val = ~in_set[u]
        wt_val = ~(in_set[aux.window[v]] & aux.win_real[v]).any()
        val = jnp.where(kind == LOG_FOLD1, fold1_val, wt_val)
        return in_set.at[jnp.where(live, v, nil)].set(live & val)

    return jax.lax.fori_loop(0, state.log_n, body, in_set)
