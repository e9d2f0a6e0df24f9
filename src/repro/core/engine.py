"""Aggregate engine — one pluggable backend for every segment reduction.

The paper's reduction rules "act very locally": every rule *test* is a
bounded neighborhood aggregate (sum / max over the masked edge list, plus
capped-window clique bits).  Rule portfolios keep growing (Großmann et al.'s
rule survey, the KaMIS reduce-and-peel line), which is only sustainable if
rules *declare* the aggregates they need and a single engine computes them —
once per sweep, on the fastest available backend — instead of every rule
family issuing its own ad-hoc segment reductions.

Three pieces:

  * **declarations** — each rule in :mod:`repro.core.rules` carries a
    ``requires`` frozenset (``@_requires``) naming the :class:`SweepCtx`
    fields its test reads.  The engine computes exactly the union of the
    scheduled rules' requirements; undeclared fields stay ``None``.
  * **schedules** — the rule order is data, not code: a named
    :class:`Schedule` lists the rule families to run and the aggregate
    *refresh* granularity:

      - ``refresh="rule"``  — aggregates recomputed before every rule
        (the seed PR's exact per-rule semantics; parity oracle in
        ``tests/seed_oracle.py``),
      - ``refresh="sweep"`` — aggregates snapshotted ONCE per sweep and
        shared by all families (the fused hot path; tests go conservatively
        stale, applications stay fresh — see the SweepCtx docstring and
        ARCHITECTURE.md for the soundness argument).

  * **backends** — :func:`aggregate` is the single entry point for segment
    reductions over the static edge list.  The rule sweep, the greedy /
    reduce-and-peel solvers and the halo-exchange conflict resolution all
    route through it:

      - ``"jnp"``     — ``jax.ops.segment_*`` (portable; XLA sort-based;
        the row array is sorted by partition construction, so the engine
        passes ``indices_are_sorted``),
      - ``"blocked"`` — blocked-ELL layout via the precomputed
        :class:`SegPlan` packing, jnp per-block reference kernels,
      - ``"pallas"``  — the same blocked-ELL layout through the fused
        multi-payload Pallas kernel (`kernels/segment_coo`), one pass over
        the packed edge blocks for all sum+max+min+bitwise-OR payloads
        (interpret mode off TPU).

    All payloads are int32, and integer addition is associative, so all
    three backends are **bit-identical** — backend choice is purely a
    performance decision.

Window bits through the edge pass.  The capped-window activity bits and the
clique test are *also* edge-local: every window entry ``window[v, i]`` is by
construction one of v's edges, so the static plan carries, per edge
``(v, u)``, the window-position bit ``wbits = Σ_i [window[v,i]=u] << i`` and
the clique-violation mask ``wnh = OR_i [window[v,i]=u] ~(adj_bits[v,i] |
1<<i)``.  One bitwise-OR column pair in the fused pass then yields

    act_bits(v) = OR_{u ∈ N(v) active} wbits(v,u)
    clique(v)   = (act_bits(v) & OR_{u active} wnh(v,u)) == 0

bit-identical to the seed's D-unrolled window gather loop (the ``need &
~have`` test distributes over the OR), with zero extra traversals.  The jnp
backend computes the same bits from the [V, D] window layout
(:func:`repro.kernels.wedge_intersect.ops.window_active_bits`) — cheaper
there than a sort-based segment pass.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ops import segment_max, segment_min, segment_sum

from repro.core import rules as R
from repro.kernels.segment_coo.ops import (
    pack_blocks, pack_blocks_stacked, segment_fused_coo,
)
from repro.kernels.segment_coo.ref import segment_or_ref
from repro.kernels.wedge_intersect import ops as W

I32_MIN = jnp.iinfo(jnp.int32).min

#: SweepCtx fields a rule may declare via @_requires (validated there).
AGGREGATES = R.SweepCtx._fields

#: Aggregate backends (see module docstring).
BACKENDS = ("jnp", "blocked", "pallas")

#: Default row-block height of the blocked-ELL packing (sublane-aligned).
R_BLK = 8

#: Candidate row-block heights for plan-build-time autotuning.
R_BLK_CANDIDATES = (8, 16, 32, 64)

#: Edge-budget alignment of the packing (int32 sublane multiple).
E_BLK_MULTIPLE = 8

#: Rule registry: schedule entries name rules; order comes from Schedule.
RULES = {
    "degree_one": R.rule_degree_one,
    "neighborhood_removal": R.rule_neighborhood_removal,
    "weight_transfer": R.rule_weight_transfer,
    "simplicial": R.rule_simplicial,
    "basic_single_edge": R.rule_basic_single_edge,
    "extended_single_edge": R.rule_extended_single_edge,
}


class Schedule(NamedTuple):
    """A rule schedule: which families run, in what order, and how often
    their test aggregates are refreshed ("rule" | "sweep")."""

    rules: Tuple[str, ...]
    refresh: str


#: The paper's §5.1 cheap-family order.
CHEAP_ORDER = (
    "degree_one",
    "neighborhood_removal",
    "weight_transfer",
    "simplicial",
    "basic_single_edge",
    "extended_single_edge",
)

#: Named schedules consumed by DisReduConfig.schedule.
SCHEDULES = {
    # seed per-rule semantics: every family sees fresh aggregates
    "cheap": Schedule(CHEAP_ORDER, "rule"),
    # fused hot path: aggregates snapshotted once per sweep (§Perf H3)
    "cheap-fused": Schedule(CHEAP_ORDER, "sweep"),
    # cheaper per-round schedules for reduce-and-greedy / reduce-and-peel:
    # no window/clique machinery at all (degree + neighborhood sums only)
    "light": Schedule(("degree_one", "neighborhood_removal"), "sweep"),
    # everything except the capped-window clique rules
    "edges-only": Schedule(
        ("degree_one", "neighborhood_removal", "basic_single_edge",
         "extended_single_edge"),
        "sweep",
    ),
}


def schedule_requires(schedule: Schedule) -> frozenset:
    """Union of the scheduled rules' aggregate declarations."""
    req = frozenset()
    for name in schedule.rules:
        req |= RULES[name].requires
    return req


# --------------------------------------------------------------------- #
# blocked-ELL plans (host-side packing of the static edge list)
# --------------------------------------------------------------------- #
class SegPlan(NamedTuple):
    """Precomputed blocked-ELL packing of one (static) row array.

    Built host-side once per Aux; the jitted sweep only gathers through it.
    ``rblk_tpl`` is a zero-size shape carrier so the (static) row-block
    height survives jit tracing without extra static arguments; ``wbits`` /
    ``wnh`` are the static per-edge window-position payloads that let the
    fused pass emit act_bits/clique (None when the plan was built without
    window structure).
    """

    edge_perm: jax.Array   # [n_blocks, E_BLK] i32 (stacked: [p, nb, E_BLK])
    lrow: jax.Array        # [n_blocks, E_BLK] i32
    rblk_tpl: jax.Array    # [r_blk, 0] i32 — zero-size static shape carrier
    wbits: Optional[jax.Array] = None  # [E] i32 window-position bits
    wnh: Optional[jax.Array] = None    # [E] i32 clique-violation masks

    @property
    def r_blk(self) -> int:
        return self.rblk_tpl.shape[0]


def autotune_r_blk(
    row: np.ndarray, n_rows: int,
    candidates: Tuple[int, ...] = R_BLK_CANDIDATES,
) -> int:
    """Pick the row-block height minimizing padded blocked-ELL traffic.

    The edge budget E_BLK is the max edge count over row blocks, so skewed
    degree distributions (GNM) blow up the padding at small R_BLK; larger
    blocks average the skew out.  Cost model = total padded items
    (n_blocks * E_BLK) — the HBM traffic this memory-bound op pays — with
    ties broken toward the smaller R_BLK (cheaper one-hot matmul).

    ``row`` may be stacked [p, E] (or a list of p row arrays): the cost
    then models the stacked packing's SHARED edge budget (max of the
    per-PE maxima), matching ``pack_blocks_stacked``.
    """
    if isinstance(row, np.ndarray) and row.ndim == 1:
        row = [row]
    best_r, best_cost = candidates[0], None
    for r in candidates:
        n_blocks = max((n_rows + r - 1) // r, 1)
        e_blk = max(
            int(np.bincount(np.asarray(rows_i) // r, minlength=n_blocks)
                .max(initial=1))
            for rows_i in row
        )
        e_blk = ((max(e_blk, 1) + E_BLK_MULTIPLE - 1) // E_BLK_MULTIPLE) \
            * E_BLK_MULTIPLE
        cost = n_blocks * e_blk
        if best_cost is None or cost < best_cost:
            best_r, best_cost = r, cost
    return best_r


def plan_edges(row: np.ndarray, gid: np.ndarray) -> np.ndarray:
    """Ids of the edges a blocked plan packs: every edge of a real row, and
    one edge of each row with ``gid < 0``.

    Such rows are the partition's nil slots; their edges are the padding
    of the stacked edge axis (row = col = nil), which ``pack_blocks`` would
    otherwise crowd into one row block, so the shared edge budget E_BLK —
    and every [n_blocks, E_BLK] plan array — would grow with the padding.
    A nil vertex is never active, so its sum payloads are 0; max/min/or
    over copies of one edge's payload equal one copy.  The reduced rows
    thus come out exactly as with every padding edge packed.
    """
    row = np.asarray(row)
    pad = np.asarray(gid)[row] < 0
    pad_ids = np.flatnonzero(pad)
    _, first = np.unique(row[pad_ids], return_index=True)
    return np.sort(np.concatenate([np.flatnonzero(~pad), pad_ids[first]]))


def _window_payloads(
    row: np.ndarray, col: np.ndarray, gid: np.ndarray,
    window: np.ndarray, win_adj_bits: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Static per-edge window payloads (host-side, once per partition).

    For edge (v, u): ``wbits`` ORs ``1 << i`` over every window position i
    of v holding u; ``wnh`` ORs the matching clique-violation masks
    ``~(win_adj_bits[v, i] | 1 << i)`` truncated to D bits (act_bits has no
    higher bits, so the truncation never changes ``act_bits & wnh``).
    Window entries are edge targets by construction (partition builds
    windows from the first D edges per row), so the OR over a vertex's
    edges recovers exactly the seed's window loop.
    """
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    D = window.shape[1]
    if D >= 32:
        raise ValueError(f"window cap D={D} must fit int32 OR payloads")
    mask_d = np.int32((1 << D) - 1)
    ent = np.asarray(window, np.int64)[row]          # [E, D]
    adj = np.asarray(win_adj_bits, np.int32)[row]    # [E, D]
    gok = np.asarray(gid, np.int32)[col] >= 0
    wbits = np.zeros(row.shape[0], np.int32)
    wnh = np.zeros(row.shape[0], np.int32)
    for i in range(D):
        m = (ent[:, i] == col) & gok
        wbits |= m.astype(np.int32) << i
        wnh |= np.where(m, ~(adj[:, i] | np.int32(1 << i)) & mask_d, 0)
    return wbits, wnh


def build_plan(
    row: np.ndarray, n_rows: int, *, r_blk: Optional[int] = R_BLK,
    col: Optional[np.ndarray] = None, gid: Optional[np.ndarray] = None,
    window: Optional[np.ndarray] = None,
    win_adj_bits: Optional[np.ndarray] = None,
) -> SegPlan:
    """Pack one PE's (or the union graph's) row array.

    ``r_blk=None`` autotunes the row-block height (see
    :func:`autotune_r_blk`).  Passing the static window structure
    (col/gid/window/win_adj_bits) additionally packs the act_bits/clique
    payloads so the fused pass can emit the window bits.  Packing is host
    work: the plan's arrays are numpy arrays, placed on the device by the
    caller (``distributed.build_union_problem``) or at the jitted call.
    """
    row = np.asarray(row)
    edges = None if gid is None else plan_edges(row, gid)
    if r_blk is None:
        r_blk = autotune_r_blk(row if edges is None else row[edges], n_rows)
    perm, lrow, _ = pack_blocks(
        row, n_rows, r_blk=r_blk, e_blk_multiple=E_BLK_MULTIPLE, edges=edges
    )
    wbits = wnh = None
    if window is not None:
        wbits, wnh = _window_payloads(row, col, gid, window, win_adj_bits)
    return SegPlan(
        edge_perm=np.asarray(perm, np.int32),
        lrow=np.asarray(lrow, np.int32),
        rblk_tpl=np.zeros((r_blk, 0), np.int32),
        wbits=wbits, wnh=wnh,
    )


def build_plan_stacked(
    rows: np.ndarray, n_rows: int, *, r_blk: Optional[int] = R_BLK,
    cols: Optional[np.ndarray] = None, gids: Optional[np.ndarray] = None,
    windows: Optional[np.ndarray] = None,
    win_adj_bits: Optional[np.ndarray] = None,
) -> SegPlan:
    """Stacked [p, ...] plan for the shard_map path (shared E_BLK).

    ``r_blk=None`` autotunes one shared height over all PEs' rows."""
    rows = np.asarray(rows)
    edges = None if gids is None else [
        plan_edges(r, g) for r, g in zip(rows, gids)]
    if r_blk is None:
        r_blk = autotune_r_blk(
            rows if edges is None else [r[e] for r, e in zip(rows, edges)],
            n_rows)
    perm, lrow, _ = pack_blocks_stacked(
        rows, n_rows, r_blk=r_blk, e_blk_multiple=E_BLK_MULTIPLE,
        edges=edges,
    )
    wbits = wnh = None
    if windows is not None:
        p = rows.shape[0]
        wb = np.zeros(rows.shape, np.int32)
        wn = np.zeros(rows.shape, np.int32)
        for i in range(p):
            wb[i], wn[i] = _window_payloads(
                rows[i], cols[i], gids[i], windows[i], win_adj_bits[i]
            )
        wbits, wnh = jnp.asarray(wb), jnp.asarray(wn)
    return SegPlan(
        edge_perm=jnp.asarray(perm, jnp.int32),
        lrow=jnp.asarray(lrow, jnp.int32),
        rblk_tpl=jnp.zeros((r_blk, 0), jnp.int32),
        wbits=wbits, wnh=wnh,
    )


# --------------------------------------------------------------------- #
# topology-keyed plan caching (the serving layer's reuse contract)
# --------------------------------------------------------------------- #
def topology_hash(row: np.ndarray, col: np.ndarray, n_rows: int) -> str:
    """Digest of the (sorted) directed edge list — weights excluded.

    Two instances share a hash iff they have the same vertex budget and the
    same edge set, which is exactly the condition under which every
    topology-derived artifact (blocked-ELL :class:`SegPlan`, window
    payloads, halo routing) is reusable verbatim; only the weight vector
    differs between requests.  The pairs are lexsorted before hashing so
    any permutation of the same edge multiset maps to one key.
    """
    row = np.ascontiguousarray(row, dtype=np.int64).reshape(-1)
    col = np.ascontiguousarray(col, dtype=np.int64).reshape(-1)
    order = np.lexsort((col, row))
    h = hashlib.sha1()
    h.update(np.int64(n_rows).tobytes())
    h.update(row[order].tobytes())
    h.update(col[order].tobytes())
    return h.hexdigest()


class PlanCacheStats(NamedTuple):
    hits: int
    misses: int
    evictions: int
    size: int
    errors: int = 0        # build() raises observed by get_or_build
    descent_hits: int = 0    # tag="descent" lookups served from cache
    descent_misses: int = 0  # tag="descent" lookups that (re)built


class PlanCache:
    """Bounded LRU cache for topology-keyed artifacts (SegPlans, packed
    serve entries).  Host-side and not thread-safe — one cache per service
    / driver.  ``max_entries`` bounds resident plans (ISSUE: eviction bound
    respected); hits refresh recency."""

    def __init__(self, max_entries: int = 256):
        if max_entries < 1:
            raise ValueError("PlanCache needs max_entries >= 1")
        self.max_entries = max_entries
        self._d: OrderedDict = OrderedDict()
        self._hits = self._misses = self._evictions = self._errors = 0
        self._descent_hits = self._descent_misses = 0

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d

    def get(self, key, tag: Optional[str] = None):
        """Value for `key` (refreshing recency) or None on miss.

        ``tag="descent"`` additionally counts the lookup in the descent
        hit/miss counters (stats telemetry for mid-solve re-packs); the
        cache contents are tag-agnostic, so a plan built by the fixed-shape
        path is a hit for a descent lookup of the same topology.
        """
        if key in self._d:
            self._d.move_to_end(key)
            self._hits += 1
            if tag == "descent":
                self._descent_hits += 1
            return self._d[key]
        self._misses += 1
        if tag == "descent":
            self._descent_misses += 1
        return None

    def put(self, key, value) -> None:
        if key in self._d:
            self._d.move_to_end(key)
        self._d[key] = value
        while len(self._d) > self.max_entries:
            self._d.popitem(last=False)
            self._evictions += 1

    def get_or_build(self, key, build, tag: Optional[str] = None):
        """Cached value for `key`, calling `build()` (and caching) on miss.

        A raising ``build()`` leaves the cache **unpoisoned**: no entry is
        inserted for `key` (a later call re-attempts the build), the miss
        is counted exactly once, the failure is counted in
        ``stats.errors``, and the exception propagates to the caller.
        """
        val = self.get(key, tag=tag)
        if val is None:
            try:
                val = build()
            except Exception:
                self._errors += 1
                raise
            self.put(key, val)
        return val

    @property
    def stats(self) -> PlanCacheStats:
        return PlanCacheStats(
            hits=self._hits, misses=self._misses,
            evictions=self._evictions, size=len(self._d),
            errors=self._errors,
            descent_hits=self._descent_hits,
            descent_misses=self._descent_misses,
        )


def plan_for(
    cache: Optional[PlanCache],
    row: np.ndarray, n_rows: int, *, r_blk: Optional[int] = R_BLK,
    col: Optional[np.ndarray] = None, gid: Optional[np.ndarray] = None,
    window: Optional[np.ndarray] = None,
    win_adj_bits: Optional[np.ndarray] = None,
    tag: Optional[str] = None,
) -> SegPlan:
    """:func:`build_plan` through a :class:`PlanCache` keyed by topology
    hash (plus the static build knobs).  ``cache=None`` builds uncached.
    ``tag="descent"`` marks the lookup in the cache's descent counters
    (shape-descent re-packs share the same key space as cold packs)."""
    if cache is None:
        return build_plan(
            row, n_rows, r_blk=r_blk, col=col, gid=gid, window=window,
            win_adj_bits=win_adj_bits,
        )
    key = (
        topology_hash(row, col if col is not None else row, n_rows),
        r_blk, window is not None,
    )
    return cache.get_or_build(key, lambda: build_plan(
        row, n_rows, r_blk=r_blk, col=col, gid=gid, window=window,
        win_adj_bits=win_adj_bits,
    ), tag=tag)


# --------------------------------------------------------------------- #
# batched plans (serving layer: one vmapped pass over many instances)
# --------------------------------------------------------------------- #
def pad_plan(plan: SegPlan, e_blk: int) -> SegPlan:
    """Pad a plan's edge budget up to `e_blk` so same-cell plans stack.

    Padding slots follow the :func:`pack_blocks` convention — edge 0 with
    ``lrow = r_blk`` — which every blocked kernel ignores, so a padded plan
    is bit-identical in effect to the original.  Padding is host work: the
    padded arrays are numpy arrays.
    """
    nb, eb = plan.edge_perm.shape
    if eb > e_blk:
        raise ValueError(f"cannot shrink plan E_BLK {eb} -> {e_blk}")
    if eb == e_blk:
        return plan
    perm = np.zeros((nb, e_blk), np.int32)
    perm[:, :eb] = np.asarray(plan.edge_perm)
    lrow = np.full((nb, e_blk), plan.r_blk, np.int32)
    lrow[:, :eb] = np.asarray(plan.lrow)
    return plan._replace(edge_perm=perm, lrow=lrow)


def stack_plans(plans: Sequence[SegPlan],
                e_blk: Optional[int] = None,
                batch_multiple: int = 1) -> SegPlan:
    """Stack same-cell plans onto a leading batch axis (shared E_BLK), on
    the host: the stacked arrays are numpy arrays.

    All plans must share ``r_blk`` and row count (same serve cell); each is
    padded to the common edge budget — `e_blk` if given (a high-water mark
    keeps recompiles monotone in the serving layer), else the batch max.
    Window payloads must be uniformly present or absent.

    ``batch_multiple`` pads the batch axis up to a multiple of the given
    count by repeating the LAST plan (phantom instances, matching the
    serving layer's repeat-last request padding) so the stacked plan
    splits evenly across a device mesh; phantom slots are sliced off by
    the caller, never read back.
    """
    if not plans:
        raise ValueError("stack_plans needs at least one plan")
    if batch_multiple < 1:
        raise ValueError(f"batch_multiple must be >= 1, got {batch_multiple}")
    if len(plans) % batch_multiple:
        pad = batch_multiple - len(plans) % batch_multiple
        plans = list(plans) + [plans[-1]] * pad
    r_blk = plans[0].r_blk
    nb = plans[0].edge_perm.shape[0]
    if any(p.r_blk != r_blk or p.edge_perm.shape[0] != nb for p in plans):
        raise ValueError("stack_plans needs plans from one serve cell "
                         "(same r_blk and row-block count)")
    has_w = [p.wbits is not None for p in plans]
    if any(h != has_w[0] for h in has_w):
        raise ValueError("mixed window payloads across batch plans")
    need = max(p.edge_perm.shape[1] for p in plans)
    if e_blk is None:
        e_blk = need
    elif e_blk < need:
        raise ValueError(f"e_blk={e_blk} below batch requirement {need}")
    padded = [pad_plan(p, e_blk) for p in plans]
    return SegPlan(
        edge_perm=np.stack([p.edge_perm for p in padded]),
        lrow=np.stack([p.lrow for p in padded]),
        rblk_tpl=np.zeros((len(plans), r_blk, 0), np.int32),
        wbits=np.stack([p.wbits for p in padded]) if has_w[0] else None,
        wnh=np.stack([p.wnh for p in padded]) if has_w[0] else None,
    )


def aggregate_batched(
    seg: Optional[jax.Array],
    n_rows: int,
    *,
    data_sum: Optional[jax.Array] = None,
    data_max: Optional[jax.Array] = None,
    data_min: Optional[jax.Array] = None,
    data_or: Optional[jax.Array] = None,
    or_nbits: int = 16,
    backend: str = "jnp",
    plan: Optional[SegPlan] = None,
    indices_are_sorted: bool = True,
) -> Tuple[Optional[jax.Array], ...]:
    """:func:`aggregate` vmapped over a leading batch axis.

    Payloads (and ``seg`` / the plan leaves, when present) carry a leading
    batch dimension; every instance is reduced independently and the
    outputs come back ``[batch, n_rows, ...]``.  Bit-identical per instance
    to the unbatched entry point on every backend — vmap only reshapes the
    integer ops, it never reassociates them.
    """
    def one(seg_i, d_sum, d_max, d_min, d_or, plan_i):
        return aggregate(
            seg_i, n_rows, data_sum=d_sum, data_max=d_max, data_min=d_min,
            data_or=d_or, or_nbits=or_nbits, backend=backend, plan=plan_i,
            indices_are_sorted=indices_are_sorted,
        )
    axes = (
        None if seg is None else 0,
        None if data_sum is None else 0,
        None if data_max is None else 0,
        None if data_min is None else 0,
        None if data_or is None else 0,
        None if plan is None else SegPlan(
            edge_perm=0, lrow=0, rblk_tpl=0,
            wbits=None if plan.wbits is None else 0,
            wnh=None if plan.wnh is None else 0,
        ),
    )
    return jax.vmap(one, in_axes=axes)(
        seg, data_sum, data_max, data_min, data_or, plan
    )


# --------------------------------------------------------------------- #
# the one segment-reduction entry point (backend dispatch)
# --------------------------------------------------------------------- #
def aggregate(
    seg: Optional[jax.Array],
    n_rows: int,
    *,
    data_sum: Optional[jax.Array] = None,
    data_max: Optional[jax.Array] = None,
    data_min: Optional[jax.Array] = None,
    data_or: Optional[jax.Array] = None,
    or_nbits: int = 16,
    backend: str = "jnp",
    plan: Optional[SegPlan] = None,
    indices_are_sorted: bool = True,
) -> Tuple[Optional[jax.Array], ...]:
    """Segment-reduce edge payloads to [n_rows] outputs on one backend.

    Returns a ``(sum, max, min, or)`` tuple (None for absent groups); 1-D
    payloads come back 1-D.  ``seg`` is the per-item segment id array,
    needed by the jnp backend only (the blocked backends traverse through
    the precomputed ``plan``; pass the plan's own row array as ``seg`` when
    both may run).  ``num_segments`` is always the static ``n_rows`` —
    every call site passes a Python int, so round-to-round shapes never
    recompile.  ``indices_are_sorted`` defaults to True because every Aux
    row array is sorted by partition construction (lexsort + nil-padding at
    the top index; offsets keep the union concatenation sorted) — pass
    False when reducing over anything else.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown aggregate backend {backend!r}")
    groups = [data_sum, data_max, data_min, data_or]
    if all(d is None for d in groups):
        raise ValueError("aggregate needs at least one payload group")

    squeeze = [d is not None and d.ndim == 1 for d in groups]
    groups = [d[:, None] if d is not None and d.ndim == 1 else d
              for d in groups]
    d_sum, d_max, d_min, d_or = groups

    if backend == "jnp":
        if seg is None:
            raise ValueError("backend 'jnp' needs the segment id array")
        kw = dict(num_segments=n_rows, indices_are_sorted=indices_are_sorted)
        outs = (
            segment_sum(d_sum, seg, **kw) if d_sum is not None else None,
            segment_max(d_max, seg, **kw) if d_max is not None else None,
            segment_min(d_min, seg, **kw) if d_min is not None else None,
            segment_or_ref(
                d_or, seg, n_rows, nbits=or_nbits,
                indices_are_sorted=indices_are_sorted,
            ) if d_or is not None else None,
        )
    else:
        if plan is None:
            raise ValueError(f"backend {backend!r} needs a SegPlan")
        outs = segment_fused_coo(
            plan.edge_perm, plan.lrow, n_rows,
            data_sum=d_sum, data_max=d_max, data_min=d_min, data_or=d_or,
            or_nbits=or_nbits, r_blk=plan.r_blk,
            force_pallas=(backend == "pallas"),
        )
    return tuple(
        o[:, 0] if o is not None and sq else o
        for o, sq in zip(outs, squeeze)
    )


# --------------------------------------------------------------------- #
# aggregate computation (SweepCtx for the scheduled rules)
# --------------------------------------------------------------------- #
@jax.named_scope("mwis.aggregate")
def compute_ctx(
    state: R.RedState,
    aux: R.Aux,
    requires: frozenset,
    *,
    backend: str = "jnp",
    plan: Optional[SegPlan] = None,
) -> R.SweepCtx:
    """Compute exactly the requested aggregates into a SweepCtx.

    `requires` and `backend` are trace-static; `plan` is a traced pytree
    (None for the jnp backend).  On the blocked/pallas backends everything —
    edge sums/maxes AND the window activity/clique bits — comes out of ONE
    fused pass over the packed edge blocks.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown aggregate backend {backend!r}")
    if backend != "jnp" and plan is None:
        raise ValueError(f"backend {backend!r} needs a SegPlan (got None)")
    V = state.w.shape[0]
    D = aux.window.shape[1]
    active = R._active(state)
    eact = R._edge_active(aux, active)
    S = deg = M = only = act_bits = clique = None

    edge_req = requires & {"S", "deg", "M", "only"}
    need_bits = bool(requires & {"act_bits", "clique"})
    payload = {
        "S": lambda: jnp.where(eact, R._aw(state, active)[aux.col], 0),
        "deg": lambda: eact.astype(jnp.int32),
        "M": lambda: jnp.where(eact, state.w[aux.col], I32_MIN),
        "only": lambda: jnp.where(eact, aux.col, -1),
    }
    sum_fields = [f for f in ("S", "deg") if f in edge_req]
    max_fields = [f for f in ("M", "only") if f in edge_req]
    data_sum = (
        jnp.stack([payload[f]() for f in sum_fields], axis=1)
        if sum_fields else None
    )
    data_max = (
        jnp.stack([payload[f]() for f in max_fields], axis=1)
        if max_fields else None
    )

    data_or = None
    if need_bits and backend != "jnp":
        if plan.wbits is None:
            raise ValueError(
                "plan lacks window payloads; build it with the window "
                "structure (col/gid/window/win_adj_bits) to compute "
                "act_bits/clique on the blocked backends"
            )
        acol = active[aux.col]
        data_or = jnp.where(
            acol[:, None], jnp.stack([plan.wbits, plan.wnh], axis=1), 0
        )

    sums = maxs = ors = None
    if data_sum is not None or data_max is not None or data_or is not None:
        sums, maxs, _, ors = aggregate(
            aux.row, V, data_sum=data_sum, data_max=data_max,
            data_or=data_or, or_nbits=max(D, 1), backend=backend, plan=plan,
        )
    out = {}
    for i, f in enumerate(sum_fields):
        out[f] = sums[:, i]
    for i, f in enumerate(max_fields):
        out[f] = maxs[:, i]
    S, deg = out.get("S"), out.get("deg")
    if "M" in out:
        M = jnp.maximum(out["M"], I32_MIN)
    if "only" in out:
        only = jnp.maximum(out["only"], 0)

    if need_bits:
        if backend == "jnp":
            act_bits = W.window_active_bits(active, aux.win_real, aux.window)
            if "clique" in requires:
                clique = W.window_clique_ok(act_bits, aux.win_adj_bits)
        else:
            act_bits = ors[:, 0]
            if "clique" in requires:
                clique = (act_bits & ors[:, 1]) == 0
    if "act_bits" not in requires:
        act_bits = None
    return R.SweepCtx(
        S=S, deg=deg, M=M, only=only, act_bits=act_bits, clique=clique
    )


# --------------------------------------------------------------------- #
# sweep driver
# --------------------------------------------------------------------- #
def sweep(
    state: R.RedState,
    aux: R.Aux,
    *,
    schedule: str = "cheap",
    backend: str = "jnp",
    plan: Optional[SegPlan] = None,
) -> R.RedState:
    """One pass of the scheduled rule families.

    refresh="sweep": the union of the schedule's aggregate requirements is
    computed ONCE and shared by every family (tests conservatively stale,
    applications fresh).  refresh="rule": each family gets its declared
    aggregates recomputed at rule entry (seed per-rule semantics).
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown rule schedule {schedule!r}; "
            f"available: {sorted(SCHEDULES)}"
        )
    sched = SCHEDULES[schedule]
    if sched.refresh == "sweep":
        ctx = compute_ctx(
            state, aux, schedule_requires(sched), backend=backend, plan=plan
        )
        for name in sched.rules:
            with jax.named_scope(f"mwis.rule.{name}"):
                state = RULES[name](state, aux, ctx)
    else:
        for name in sched.rules:
            ctx = compute_ctx(
                state, aux, RULES[name].requires, backend=backend, plan=plan
            )
            with jax.named_scope(f"mwis.rule.{name}"):
                state = RULES[name](state, aux, ctx)
    return state
