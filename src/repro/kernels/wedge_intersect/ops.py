"""Public API: capped-window machinery (per-edge intersections + per-vertex
activity/clique bitmasks), dispatching pallas/jnp.

The [V, D] window layout is the home of everything capped-neighborhood:

  * :func:`common_neighbor_stats` — weighted/active window intersection per
    edge (single-edge rules),
  * :func:`window_active_bits` / :func:`window_clique_ok` — the vectorized
    window activity + clique predicates.  These are the FRESH-status forms
    used by rule *applications* (and by the engine's jnp backend); the
    aggregate engine's blocked/pallas backends compute the same bits through
    the fused edge pass instead (static window-position payloads in the
    SegPlan — see ``repro.core.engine``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, use_pallas
from repro.kernels.wedge_intersect.kernel import wedge_intersect
from repro.kernels.wedge_intersect.ref import common_neighbor_stats_ref


def common_neighbor_stats(
    window: jax.Array,   # [V, D] capped neighbor lists (nil padded)
    weights: jax.Array,  # [V] current weights
    active: jax.Array,   # [V] bool
    row: jax.Array,      # [E]
    col: jax.Array,      # [E]
    *,
    force_pallas: bool | None = None,
):
    """(C[e], K[e]) = weighted/active common-neighborhood per edge.

    Entries are drawn from W(row); membership is tested against W(col), so
    the result is the capped lower bound the single-edge rules require.
    The [E, D] gathers happen inside the chosen backend branch: the jnp
    reference path masks weights by the match directly instead of
    materializing separate masked-weight/activity operands first.
    """
    enable = use_pallas() if force_pallas is None else force_pallas
    if enable:
        wu = window[row]
        wv = window[col]
        ent_act = active[wu]
        awu = jnp.where(ent_act, weights[wu], 0).astype(jnp.int32)
        return wedge_intersect(
            wu, wv, awu, ent_act.astype(jnp.int32),
            interpret=interpret_mode(),
        )
    return common_neighbor_stats_ref(window, weights, active, row, col)


def window_active_bits(
    active: jax.Array,    # [V] bool (status == UNDECIDED)
    win_real: jax.Array,  # [V, D] bool static: gid[window] >= 0
    window: jax.Array,    # [V, D] capped neighbor lists
) -> jax.Array:
    """[V] i32 — bit i set iff window[v, i] is an active real vertex.

    Vectorized form of the seed's D-unrolled loop: one [V, D] gather of the
    activity (the real-vertex mask is static), bits are disjoint per
    position so the OR is a plain sum."""
    D = window.shape[1]
    ent_ok = active[window] & win_real                          # [V, D]
    shifts = jnp.arange(D, dtype=jnp.int32)[None, :]
    return (ent_ok.astype(jnp.int32) << shifts).sum(axis=1)


def window_clique_ok(
    act_bits: jax.Array,      # [V] i32 window activity bits
    win_adj_bits: jax.Array,  # [V, D] i32 static pairwise adjacency bits
) -> jax.Array:
    """[V] bool — do the *active* window entries form a clique?

    Exact when win_complete (window = full static neighbor list); the caller
    must gate on win_complete.  Ghost pairs have no stored edge, so ≥2
    active ghost neighbors naturally fail — matching "a clique in G_i
    contains at most one ghost"."""
    D = win_adj_bits.shape[1]
    shifts = jnp.arange(D, dtype=jnp.int32)[None, :]
    active_i = ((act_bits[:, None] >> shifts) & 1) == 1        # [V, D]
    need = act_bits[:, None] & ~(jnp.int32(1) << shifts)
    bad = active_i & ((need & ~win_adj_bits) != 0)
    return ~bad.any(axis=1)
