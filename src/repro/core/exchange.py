"""Border exchange — §5.2 "Communicating Reduction Progress" in JAX.

Two message types, exactly as the paper defines them:

  (1) weight decrease  — interface weights are re-published so ghost copies
      stay valid upper bounds (Lemma 4.2),
  (2) vertex status    — excluded / proposed-to-include updates, with the
      Lemma 4.4/4.5 rank tie-breaking for conflicting include proposals.

Collective realisations (both produce identical (gw, gs) per ghost):

  * ``allgather`` — every PE publishes its interface *board*; ghosts index
    their owner's board entry.  O(p·B) bytes per PE; simple; the baseline.
  * ``a2a``       — padded per-destination buckets via ``lax.all_to_all``;
    each PE receives only the entries it actually ghosts.  O(p·S) bytes
    with S = max pairwise halo — the bandwidth-optimal variant (§Perf).

Every function exists in two layouts driven by the same `reconcile` core:

  * per-PE layout (inside ``shard_map``; lax collectives), and
  * "union" layout — all PEs stacked into one block-diagonal graph on a
    single device; collectives become array indexing.  This is the CPU test
    / simulation path: it executes the *same SPMD semantics* deterministically
    without needing p host devices.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine as E
from repro.core import rules as R
from repro.core.partition import PartitionedGraph

UNDECIDED, INCLUDED, EXCLUDED, FOLDED = 0, 1, 2, 3


class Halo(NamedTuple):
    """Halo routing (one PE's slice, or stacked [p, ...] for the union)."""

    iface_slots: jax.Array       # [B] local idx of board slots (pad = nil)
    ghost_vertex: jax.Array      # [G] vertex index of each ghost slot
    ghost_owner_pe: jax.Array    # [G] rank owning the ghost (pad = 0)
    ghost_owner_slot: jax.Array  # [G] slot in owner's board (pad = 0)
    ghost_valid: jax.Array       # [G] bool
    send_slot: jax.Array         # [p, S] board slots per destination (pad = B)
    recv_ghost: jax.Array        # [p, S] ghost slot per source (pad = G)


def make_halo(pg: PartitionedGraph, pe: int | None = None) -> Halo:
    """pe=None → stacked [p, ...] halo as host (numpy) arrays (union layout
    uses vertex offsets; ``distributed.upload_union_problem`` places it)."""
    import numpy as np

    L, G, V = pg.L, pg.G, pg.V
    if pe is None:
        off = (np.arange(pg.p, dtype=np.int64) * V)[:, None]
        iface = np.where(
            pg.iface_slots < pg.nil, pg.iface_slots + off, pg.p * V
        )
        gvert = off + L + np.arange(G)[None, :]
        return Halo(
            iface_slots=np.asarray(iface, np.int32),
            ghost_vertex=np.asarray(gvert, np.int32),
            ghost_owner_pe=np.asarray(
                np.maximum(pg.owner_pe[:, L : L + G], 0), np.int32
            ),
            ghost_owner_slot=np.asarray(pg.ghost_owner_slot, np.int32),
            ghost_valid=np.asarray(pg.is_ghost[:, L : L + G]),
            send_slot=np.asarray(pg.send_slot, np.int32),
            recv_ghost=np.asarray(pg.recv_ghost, np.int32),
        )
    return Halo(
        iface_slots=jnp.asarray(pg.iface_slots[pe], jnp.int32),
        ghost_vertex=jnp.asarray(L + jnp.arange(G), jnp.int32),
        ghost_owner_pe=jnp.asarray(
            jnp.maximum(jnp.asarray(pg.owner_pe[pe, L : L + G]), 0), jnp.int32
        ),
        ghost_owner_slot=jnp.asarray(pg.ghost_owner_slot[pe], jnp.int32),
        ghost_valid=jnp.asarray(pg.is_ghost[pe, L : L + G]),
        send_slot=jnp.asarray(pg.send_slot[pe], jnp.int32),
        recv_ghost=jnp.asarray(pg.recv_ghost[pe], jnp.int32),
    )


# --------------------------------------------------------------------- #
# reconcile: apply (gw, gs) ghost updates + include-conflict tie-breaking
# --------------------------------------------------------------------- #
def reconcile(
    state: R.RedState,
    aux: R.Aux,
    ghost_vertex: jax.Array,
    ghost_valid: jax.Array,
    gw: jax.Array,
    gs: jax.Array,
    *,
    backend: str = "jnp",
    plan: Optional[E.SegPlan] = None,
) -> Tuple[R.RedState, jax.Array]:
    """Apply board-derived ghost weight/status updates.

    Conflicting include proposals across a cut edge can only be the
    isolated-equal-weight-edge case (Lemma 4.4); both sides deterministically
    keep the endpoint owned by the *smaller* rank (Lemma 4.5).
    Returns (state, changed).

    All conflict reductions are keyed by ``aux.row`` — the sorted segment
    axis the SegPlan packs — so they route through the same blocked pass as
    the rule aggregates.  The partition stores both directions of every
    edge, so the seed's col-keyed existence tests are re-expressed with
    swapped endpoint payloads (identical booleans over a symmetric edge
    set).  ``num_segments`` is the static V everywhere.
    """
    V = state.w.shape[0]
    nilv = V - 1

    # Scatter board values into V-sized arrays (ghost slots only).
    tgt = jnp.where(ghost_valid, ghost_vertex, nilv)
    bw = jnp.full(V, jnp.iinfo(jnp.int32).max, jnp.int32).at[tgt].set(
        jnp.where(ghost_valid, gw, jnp.iinfo(jnp.int32).max)
    )
    bs = jnp.full(V, -1, jnp.int32).at[tgt].set(
        jnp.where(ghost_valid, gs.astype(jnp.int32), -1)
    )

    status = state.status
    rank_r = aux.owner_rank[aux.row]
    rank_c = aux.owner_rank[aux.col]

    # --- include-proposal conflicts over cut edges -------------------- #
    ghost_inc = bs == INCLUDED                       # [V] board says included
    prop_local = (status == INCLUDED) & aux.is_iface
    # (a) local proposal v = row loses iff a proposing ghost neighbor's
    #     owner has the smaller rank
    v_lose_e = (
        prop_local[aux.row] & ghost_inc[aux.col]
        & (aux.gid[aux.col] >= 0) & (rank_c < rank_r)
    )
    # (b) the ghost's proposal u = row loses iff our local proposal has the
    #     smaller rank
    u_lose_e = (
        ghost_inc[aux.row] & prop_local[aux.col]
        & (aux.gid[aux.row] >= 0) & (rank_c < rank_r)
    )
    _, losses, _, _ = E.aggregate(
        aux.row, V,
        data_max=jnp.stack([v_lose_e, u_lose_e], axis=1).astype(jnp.int32),
        backend=backend, plan=plan,
    )
    v_lose = losses[:, 0] > 0
    u_lose = losses[:, 1] > 0
    status = jnp.where(
        v_lose & (status == INCLUDED), jnp.int8(EXCLUDED), status
    )

    # --- ghost status update ------------------------------------------ #
    is_ghost_slot = bs >= 0
    new_ghost = jnp.where(
        (bs == INCLUDED) & ~u_lose,
        jnp.int32(INCLUDED),
        jnp.where(
            (bs == EXCLUDED) | (bs == FOLDED) | ((bs == INCLUDED) & u_lose),
            jnp.int32(EXCLUDED),
            status.astype(jnp.int32),  # owner still UNDECIDED: keep local view
        ),
    )
    status2 = jnp.where(is_ghost_slot, new_ghost.astype(jnp.int8), status)

    # --- weight decrease (owner is authoritative; monotone) ------------ #
    w2 = jnp.where(is_ghost_slot, jnp.minimum(state.w, bw), state.w)

    # --- exclude local active neighbors of newly-included ghosts ------- #
    ginc_now = is_ghost_slot & (status2 == INCLUDED)
    _, hit_m, _, _ = E.aggregate(
        aux.row, V, data_max=ginc_now[aux.col].astype(jnp.int32),
        backend=backend, plan=plan,
    )
    status3 = jnp.where(
        (hit_m > 0) & (status2 == UNDECIDED) & aux.is_local,
        jnp.int8(EXCLUDED), status2,
    )

    changed = (status3 != state.status).any() | (w2 != state.w).any()
    new_state = state._replace(w=w2, status=status3)
    return new_state, changed


# --------------------------------------------------------------------- #
# board construction + the two collective realisations
# --------------------------------------------------------------------- #
def _board(state: R.RedState, iface_slots: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Board values; padded slots index nil → weight 0 / EXCLUDED (ignored
    because padded ghosts are invalid on the receiving side)."""
    bw = state.w[iface_slots]
    bs = state.status[iface_slots]
    return bw, bs


@jax.named_scope("mwis.exchange")
def exchange_shmap(
    state: R.RedState, aux: R.Aux, halo: Halo, *, axis: str = "pe",
    method: str = "allgather",
    backend: str = "jnp", plan: Optional[E.SegPlan] = None,
) -> Tuple[R.RedState, jax.Array]:
    """Per-PE exchange with lax collectives (inside shard_map)."""
    bw, bs = _board(state, halo.iface_slots)
    if method == "allgather":
        boards_w = jax.lax.all_gather(bw, axis)                  # [p, B]
        boards_s = jax.lax.all_gather(bs, axis)
        gw = boards_w[halo.ghost_owner_pe, halo.ghost_owner_slot]
        gs = boards_s[halo.ghost_owner_pe, halo.ghost_owner_slot]
    elif method == "a2a":
        B = bw.shape[0]
        bw_ext = jnp.concatenate([bw, jnp.zeros(1, bw.dtype)])
        bs_ext = jnp.concatenate([bs, jnp.full(1, EXCLUDED, bs.dtype)])
        send_w = bw_ext[halo.send_slot]                          # [p, S]
        send_s = bs_ext[halo.send_slot]
        recv_w = jax.lax.all_to_all(send_w, axis, 0, 0, tiled=True)
        recv_s = jax.lax.all_to_all(send_s, axis, 0, 0, tiled=True)
        G = halo.ghost_vertex.shape[0]
        gw = jnp.zeros(G + 1, jnp.int32).at[halo.recv_ghost.reshape(-1)].set(
            recv_w.reshape(-1)
        )[:G]
        gs = jnp.zeros(G + 1, jnp.int8).at[halo.recv_ghost.reshape(-1)].set(
            recv_s.reshape(-1)
        )[:G]
    else:
        raise ValueError(f"unknown exchange method {method!r}")
    return reconcile(
        state, aux, halo.ghost_vertex, halo.ghost_valid, gw, gs,
        backend=backend, plan=plan,
    )


def union_boards(
    state: R.RedState, halo: Halo
) -> Tuple[jax.Array, jax.Array]:
    """Every PE's published interface board in the union layout.

    Returns ``(boards_w, boards_s)``, both [p, B] — the message each PE
    *would* put on the wire this round.  This is the fault-injection seam:
    :mod:`repro.distributed.fault` snapshots these boards per round and
    feeds delayed/dropped variants back through
    :func:`reconcile_union_boards`, which is exactly a late/lost message
    in the bounded-staleness exchange (§5.4).
    """
    # halo.iface_slots is [p, B] with union indices (pad = p*V, clamped).
    nil_u = state.w.shape[0] - 1
    slots = jnp.minimum(halo.iface_slots, nil_u)
    return state.w[slots], state.status[slots]


def reconcile_union_boards(
    state: R.RedState, aux: R.Aux, halo: Halo,
    boards_w: jax.Array, boards_s: jax.Array, *,
    backend: str = "jnp", plan: Optional[E.SegPlan] = None,
) -> Tuple[R.RedState, jax.Array]:
    """Apply a full [p, B] board set (possibly stale) to the union state."""
    gw = boards_w[halo.ghost_owner_pe, halo.ghost_owner_slot]  # [p, G]
    gs = boards_s[halo.ghost_owner_pe, halo.ghost_owner_slot]
    return reconcile(
        state, aux,
        halo.ghost_vertex.reshape(-1),
        halo.ghost_valid.reshape(-1),
        gw.reshape(-1), gs.reshape(-1),
        backend=backend, plan=plan,
    )


@jax.named_scope("mwis.exchange")
def exchange_union(
    state: R.RedState, aux: R.Aux, halo: Halo, *, p: int,
    backend: str = "jnp", plan: Optional[E.SegPlan] = None,
) -> Tuple[R.RedState, jax.Array]:
    """Union-layout exchange: 'collectives' are plain indexing across the
    stacked [p, ...] halo (single-device simulation of the SPMD program)."""
    boards_w, boards_s = union_boards(state, halo)
    return reconcile_union_boards(
        state, aux, halo, boards_w, boards_s, backend=backend, plan=plan,
    )
