"""Local reduction phase (§5.1): exhaustive fixed-order rule application.

Per PE, rules sweep until no rule fires — the paper restarts from the first
rule after every successful application; our batched equivalent applies all
scheduled cheap families per sweep and only pays for Distributed Heavy
Vertex (the expensive exact-sub-MWIS rule, last in the paper's order too) on
sweeps where the cheap families made no progress.

Which families run, and how their test aggregates are computed, is data:
the `schedule` names an :data:`repro.core.engine.SCHEDULES` entry and the
`backend`/`plan` pair picks the segment-reduction backend (see engine.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine as E
from repro.core import rules as R
from repro.core.partition import PartitionedGraph


def make_aux(pg: PartitionedGraph, pe: int | None = None) -> R.Aux:
    """Build the static Aux pytree; pe=None keeps the stacked [p, ...] axis."""
    sl = (slice(None),) if pe is None else (pe,)

    def take(a):
        return jnp.asarray(a[sl])

    return R.Aux(
        row=take(pg.row), col=take(pg.col), gid=take(pg.gid),
        is_local=take(pg.is_local), is_iface=take(pg.is_iface),
        owner_rank=take(pg.owner_pe),
        window=take(pg.window), win_complete=take(pg.win_complete),
        win_adj_bits=take(pg.win_adj_bits), edge_common=take(pg.edge_common),
        **{k: take(getattr(pg, k)) for k in R.STATIC_MASKS},
    )


def local_reduce(
    state: R.RedState,
    aux: R.Aux,
    *,
    heavy_k: int = 8,
    use_heavy: bool = True,
    max_sweeps: int = 10_000,
    schedule: str = "cheap",
    backend: str = "jnp",
    plan: Optional[E.SegPlan] = None,
) -> R.RedState:
    """Run rule sweeps to the local fixpoint (lax.while_loop)."""

    def body(carry):
        state, _ = carry
        state = state._replace(changed=jnp.zeros((), bool))
        state = E.sweep(
            state, aux, schedule=schedule, backend=backend, plan=plan
        )
        if use_heavy:
            with jax.named_scope("mwis.rule.heavy"):
                state = jax.lax.cond(
                    state.changed,
                    lambda s: s,
                    lambda s: R.rule_heavy_vertex(s, aux, heavy_k),
                    state,
                )
        return state, carry[1] + 1

    def cond(carry):
        state, it = carry
        return state.changed & (it < max_sweeps)

    state = state._replace(changed=jnp.ones((), bool))
    state, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int32))
    )
    return state


@functools.partial(
    jax.jit, static_argnames=("heavy_k", "use_heavy", "schedule", "backend")
)
def _reduce_jit(w0, is_local, is_ghost, aux, plan, heavy_k, use_heavy,
                schedule, backend):
    state = R.init_state(w0, is_local, is_ghost)
    return local_reduce(
        state, aux, heavy_k=heavy_k, use_heavy=use_heavy,
        schedule=schedule, backend=backend, plan=plan,
    )


def reduce_single_pe(
    pg: PartitionedGraph, *, heavy_k: int = 8, use_heavy: bool = True,
    schedule: str = "cheap", backend: str = "jnp",
    r_blk: int | None = None,
) -> Tuple[R.RedState, R.Aux]:
    """Single-PE (p must be 1) reduction — the sequential-semantics entry
    point used by tests and as the p=1 baseline of the scaling benches."""
    assert pg.p == 1, "reduce_single_pe expects an unpartitioned graph"
    aux = make_aux(pg, pe=0)
    plan = None if backend == "jnp" else E.build_plan(
        pg.row[0], pg.V, r_blk=r_blk,
        col=pg.col[0], gid=pg.gid[0], window=pg.window[0],
        win_adj_bits=pg.win_adj_bits[0],
    )
    state = _reduce_jit(
        jnp.asarray(pg.w0[0]),
        jnp.asarray(pg.is_local[0]),
        jnp.asarray(pg.is_ghost[0]),
        aux, plan, heavy_k, use_heavy, schedule, backend,
    )
    return state, aux
