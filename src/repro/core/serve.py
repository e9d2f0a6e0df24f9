"""MWIS-as-a-service: batched many-instance solving on the unified engine.

The paper's distributed reductions shrink ONE giant instance across many
PEs; the production inverse is thousands of small/medium instances per
second (conflict scheduling, ad-slot auctions, spectrum allocation).  This
module is that front end, built on three observations:

  * **shape bucketing** — ``partition_graph(..., pad_to=cell)`` already
    pads an instance into a static shape cell, so every instance admitted
    to one cell is the same pytree of array shapes; a batch of them is one
    leading axis.  The bucket table is the ``kind="serve"`` rows of
    :data:`repro.configs.base.MWIS_SHAPES` (smallest cell with
    ``L >= n`` and ``E >= 2m`` wins).
  * **vmap over the union path** — the solver bodies are already traceable
    array-in/array-out (:func:`repro.core.solvers.solve_union_arrays`), so
    the batched solver is literally ``jax.vmap`` of the single-instance
    program.  Every op in the solve is integer/bool, so the batched run is
    **bit-identical** per instance to the unbatched path on every backend
    (vmap reshapes the ops, it never reassociates them); while-loop trip
    counts couple across the batch, but every round body is idempotent at
    its fixpoint, so extra rounds are no-ops.
  * **topology-keyed reuse** — the expensive host-side work (partition,
    window payloads, blocked-ELL ``SegPlan`` packing + autotune) depends
    only on the edge list, not the weights.  A :class:`~repro.core.engine.
    PlanCache` keyed by :func:`~repro.core.engine.topology_hash` makes a
    repeated topology (the common case: the same conflict graph re-solved
    with fresh bids every auction round) skip straight to the device call
    with only a weight-vector refill.

Blocked/pallas batching: all plans in one cell share ``r_blk`` (fixed per
cell) and row count, so they stack after padding to a shared edge budget.
The shared E_BLK is a per-(cell, batch) **high-water mark** — it only
grows, so recompiles are monotone and bounded, and the padded slots are
by construction ignored by the kernels (bit-identity is preserved).

Multi-device serving (the throughput lever past one accelerator):

  * **batch-axis sharding** — every instance in a stacked chunk is
    independent, so the batch axis shards trivially over a flat ``serve``
    mesh (:func:`repro.launch.mesh.make_serve_mesh`): the stacked arrays
    are ``jax.device_put`` with a ``NamedSharding`` on their leading axis
    and the jitted vmapped program runs SPMD (the only cross-device
    traffic is the while-loop condition's OR-reduce, which only couples
    trip counts — every round body is idempotent at its fixpoint, so the
    per-instance results stay **bit-identical** to the single-device
    path).  Batch sizes are rounded up to a multiple of the active device
    count (phantom repeat-last instances, discarded on fetch) so shards
    always split evenly and a ragged tail never compiles a one-off shape.
  * **overlapped host pipeline** — within one ``solve_batch`` call the
    chunks are double-buffered: while the device solves chunk *k*, the
    host packs, stacks and transfers chunk *k+1* (jax dispatch is async,
    so the weight refill, the host-side stack and the one H2D of the next
    chunk hide under the in-flight solve instead of serializing with it —
    the same communication/computation overlap DisReduA uses between PEs,
    applied to the host→device edge).  Per-stage wall time (pack / transfer /
    solve / fetch) and the achieved overlap ratio are recorded in
    ``MWISService.stats``, from the same intervals as the ``mwis.serve.*``
    host spans (:mod:`repro.core.spans`).

Donation: the stacked weight plane is donated to the jitted batched
solver, whose final residual weights reuse its buffer.  A donated plane is
dead after its launch, so weights live on the host (``Topology.prob.w0``
is a numpy plane) and every launch — retries and fallbacks included —
stages a fresh device plane from them.

Robustness (the hardened-serving layer):

  * **admission** — requests pass :func:`repro.core.validate.canonicalize`
    (``ServeConfig.validate``): harmless defects (self-loops, duplicate or
    asymmetric directed edges, unsorted rows) are repaired, rejects
    (NaN/negative/overflow weights, broken CSR, out-of-range indices)
    become structured per-request errors with stable reason codes.
  * **per-request fault isolation** — `solve_batch` NEVER raises for a bad
    instance; every :class:`ServeResult` carries ``ok``/``reason``/
    ``error``, so one poisoned request (oversize, malformed, unpackable)
    degrades to an error entry while every healthy instance in the batch
    still solves bit-identically to the pre-hardening path.  Oversize
    instances are rejected with ``reason="oversize"`` — route those
    through the distributed path (:func:`repro.core.solvers.solve`).
  * **backend fallback** — a compile/runtime failure of the configured
    backend falls down the chain ``pallas → blocked → jnp`` (all three are
    bit-identical by the engine contract, so degradation is performance
    only); failed plan builds stay out of the `PlanCache`
    (`get_or_build` never caches a raising build), and fallbacks are
    counted in ``MWISService.stats``.
  * **verified outputs** — ``ServeConfig.verify`` ∈ ``off | sample |
    full`` audits results post-solve (:func:`repro.core.validate.
    verify_result`): independence + weight recomputation.  ``sample``
    checks the first request of every device chunk; ``full`` checks all.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import base as CFG
from repro.core import engine as E
from repro.core import solvers as SOL
from repro.core import validate as V
from repro.core.distributed import pack_union_problem
from repro.core.graph import Graph
from repro.core.partition import partition_graph
from repro.core.spans import span

#: Backend degradation order: a failing backend falls to the next entry.
FALLBACK_CHAIN = {
    "pallas": ("pallas", "blocked", "jnp"),
    "blocked": ("blocked", "jnp"),
    "jnp": ("jnp",),
}


class ServeCell(NamedTuple):
    """One resolved serving bucket (a kind="serve" MWIS_SHAPES row)."""

    name: str
    L: int      # max vertices
    E: int      # max directed edges (2m)
    G: int      # ghost pad (p=1: floor only)
    B: int      # board pad
    S: int      # send-list pad
    D: int      # window cap
    Dc: int     # common-neighborhood cap
    schedule: str
    r_blk: int  # blocked-ELL row-block height (shared across the cell)
    e_blk: int  # blocked-ELL edge-budget floor (high-water mark seed)
    serve_devices: Optional[int] = None  # batch-axis device cap (None=mesh)
    pipeline: bool = True                # overlapped pack/transfer opt-out


def _cells_of_kind(kind: str) -> Tuple[ServeCell, ...]:
    cells = []
    for name, meta in CFG.MWIS_SHAPES.items():
        if meta.get("kind") != kind:
            continue
        seg = meta.get("seg_blk", {})
        cells.append(ServeCell(
            name=name, L=meta["L"], E=meta["E"], G=meta["G"], B=meta["B"],
            S=meta["S"], D=meta["D"], Dc=meta["Dc"],
            schedule=meta.get("schedule", "cheap-fused"),
            r_blk=seg.get("r_blk", E.R_BLK),
            e_blk=seg.get("e_blk", E.E_BLK_MULTIPLE),
            serve_devices=meta.get("serve_devices"),
            pipeline=meta.get("pipeline", True),
        ))
    cells.sort(key=lambda c: (c.L, c.E))
    return tuple(cells)


def serve_cells() -> Tuple[ServeCell, ...]:
    """The bucket table, ascending by capacity."""
    return _cells_of_kind("serve")


def descent_entry_cells() -> Tuple[ServeCell, ...]:
    """kind="descent" MWIS_SHAPES rows — oversize *entry* shapes for the
    staged path (never batched; a solve entering here descends into the
    serve cells as soon as reduction shrinks the kernel)."""
    return _cells_of_kind("descent")


def bucket_for(n: int, directed_edges: int,
               cells: Optional[Sequence[ServeCell]] = None) -> ServeCell:
    """Smallest cell admitting an instance with n vertices / 2m directed
    edges; raises ValueError (naming the limits) when none fits."""
    cells = tuple(cells) if cells is not None else serve_cells()
    for c in cells:
        if n <= c.L and directed_edges <= c.E:
            return c
    big = cells[-1] if cells else None
    raise ValueError(
        f"instance (n={n}, directed_edges={directed_edges}) exceeds every "
        f"serve cell; largest is "
        f"{big.name if big else '<none>'} "
        f"(L={big.L if big else 0}, E={big.E if big else 0}) — route giant "
        f"instances through the distributed path (repro.core.solvers.solve)"
    )


class Topology(NamedTuple):
    """Cached per-topology artifact: everything derived from the edge list.

    ``prob`` is a p=1 UnionProblem of host (numpy) arrays whose w0 is a
    placeholder — requests refill only the weight plane.  ``n`` is the
    true (unpadded) vertex count; members/weights are read back as
    ``members[:n]``.
    """

    prob: SOL.UnionProblem
    n: int


def _pack_topology(g: Graph, cell: ServeCell, backend: str) -> Topology:
    pg = partition_graph(
        g, 1, window_cap=cell.D, common_cap=cell.Dc,
        pad_to=dict(L=cell.L, G=cell.G, E=cell.E, B=cell.B, S=cell.S),
    )
    if pg.L != cell.L or pg.E != cell.E or pg.G != cell.G:
        raise ValueError(
            f"instance broke out of cell {cell.name}: padded "
            f"(L={pg.L}, E={pg.E}, G={pg.G}) vs cell "
            f"(L={cell.L}, E={cell.E}, G={cell.G})"
        )
    # the cache keeps the host pack: chunks are stacked on the host
    prob = pack_union_problem(
        pg, backend, None if backend == "jnp" else cell.r_blk
    )
    return Topology(prob=prob, n=g.n)


def _weight_plane(g: Graph, cell: ServeCell) -> np.ndarray:
    w0 = np.zeros(cell.L + cell.G + 1, dtype=np.int32)
    w0[: g.n] = g.weights
    return w0


class ServeResult(NamedTuple):
    """One request's outcome.  ``ok=False`` results carry a stable
    ``reason`` code (:mod:`repro.core.validate` REASON_*) and a
    human-readable ``error``; their mask is all-False and weight 0.
    ``reason="oversize"`` means the instance exceeds every serve cell —
    route it through the distributed path, ``repro.core.solvers.solve``.
    """

    members: np.ndarray   # [n] bool — the independent set
    weight: int           # its weight under the request's weight vector
    ok: bool = True
    reason: Optional[str] = None   # machine-readable error code
    error: Optional[str] = None    # human-readable detail


def _error_result(n: int, reason: str, detail: str) -> ServeResult:
    return ServeResult(
        members=np.zeros(max(n, 0), dtype=bool), weight=0,
        ok=False, reason=reason, error=f"{reason}: {detail}",
    )


class _Staged(NamedTuple):
    """A chunk stacked to its static batch shape and placed on the serve
    mesh (device_put already issued), ready to launch."""

    cell: ServeCell
    backend: str
    topos: Tuple[Topology, ...]   # the real (unpadded) chunk members
    args: tuple                   # (w0s, is_local, is_ghost, auxs, halos,
                                  #  plans) — leading axis = static batch
    e_blk: int
    rec: dict                     # per-chunk stage-timing record


class _Inflight(NamedTuple):
    """A launched chunk whose result is an unretired jax future."""

    staged: _Staged
    members: jax.Array            # async [bt, L+G+1] bool
    solving: span                 # the open mwis.serve.solve span


class _Pending(NamedTuple):
    """A dispatched pipeline chunk awaiting retirement.  ``inflight`` is
    None when dispatch itself failed — the retire step then re-runs the
    chunk through the synchronous fallback-chain path."""

    inflight: Optional[_Inflight]
    cell: ServeCell
    good: List[int]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving knobs (algo/backend/schedule as in DisReduConfig)."""

    algo: str = "rg"              # greedy | rg | rnp
    backend: str = "jnp"          # jnp | blocked | pallas
    schedule: Optional[str] = None  # None -> per-cell default
    heavy_k: int = 8
    use_heavy: bool = True
    max_rounds: int = 64
    cache_entries: int = 256      # topology-cache bound (LRU)
    max_batch: int = 64           # largest admitted device batch
    validate: bool = True         # canonicalize/reject requests on admission
    verify: str = "off"           # post-solve audit: off | sample | full
    fallback: bool = True         # walk FALLBACK_CHAIN on backend failure
    # --- multi-device batch sharding + overlapped host pipeline ------- #
    devices: Optional[int] = None  # serve-mesh size (None = every visible
                                   # device; > visible raises at init)
    pipeline: bool = True          # overlap pack/H2D of chunk k+1 with the
                                   # in-flight solve of chunk k
    # --- shape descent (solvers.solve_staged) ------------------------- #
    descent: str = "off"          # off | auto — big cells take the staged
                                  # path and shrink mid-solve
    descent_min_L: int = 1024     # smallest cell L routed through descent
                                  # (default: serve_m and up)
    descent_every: int = 2        # stage length between descent checks


class MWISService:
    """Bucketing → plan cache → vmapped engine → donation.

    ``solve_batch`` groups requests by serve cell, pads each group to a
    static batch size (:data:`repro.configs.base.MWIS_SERVE_BATCH_SIZES`),
    and dispatches one jitted vmapped solve per (cell, batch) program.
    Results come back in request order.
    """

    def __init__(self, cfg: ServeConfig = ServeConfig(),
                 cells: Optional[Sequence[ServeCell]] = None):
        if cfg.algo not in ("greedy", "rg", "rnp"):
            raise ValueError(f"unknown serve algo {cfg.algo!r}")
        if cfg.backend not in E.BACKENDS:
            raise ValueError(
                f"unknown backend {cfg.backend!r}; available: {E.BACKENDS}"
            )
        if cfg.verify not in ("off", "sample", "full"):
            raise ValueError(
                f"unknown verify mode {cfg.verify!r}; "
                "available: ('off', 'sample', 'full')"
            )
        if cfg.descent not in ("off", "auto"):
            raise ValueError(
                f"unknown descent mode {cfg.descent!r}; "
                "available: ('off', 'auto')"
            )
        visible = jax.device_count()
        if cfg.devices is not None and not 1 <= cfg.devices <= visible:
            raise ValueError(
                f"serve devices={cfg.devices} exceeds the {visible} "
                f"visible jax device(s) — launch with more devices or set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{cfg.devices} for CPU testing"
            )
        self.cfg = cfg
        self.cells = tuple(cells) if cells is not None else serve_cells()
        self.descent_cells = descent_entry_cells() \
            if cfg.descent == "auto" else ()
        if not self.cells:
            raise ValueError("no serve cells configured (MWIS_SHAPES has "
                             "no kind='serve' rows)")
        self.cache = E.PlanCache(max_entries=cfg.cache_entries)
        self._batched_fns: Dict[tuple, object] = {}
        self._eblk_hwm: Dict[str, int] = {}
        self.compiles = 0
        # active backend: starts at cfg.backend, demoted down
        # FALLBACK_CHAIN when a program build/execute fails
        self._backend = cfg.backend
        self._ndev = cfg.devices if cfg.devices is not None else visible
        self._meshes: Dict[int, object] = {}   # device count -> serve Mesh
        self._stage_totals = dict(pack=0.0, transfer=0.0, solve=0.0,
                                  fetch=0.0)       # cumulative ms per stage
        self._stage_log: deque = deque(maxlen=2048)  # per-chunk timing recs
        self._wall_s = 0.0                 # chunk-processing wall seconds
        self.counters = dict(
            requests=0, rejected=0, repaired=0, pack_errors=0,
            solve_errors=0, fallbacks=0, verify_checked=0,
            verify_failures=0, descent_solves=0, descents=0,
            oversize_admitted=0, chunks=0, pipelined_chunks=0,
            pipeline_retries=0,
        )
        self.events: List[tuple] = []   # (kind, detail) robustness log

    # ------------------------------------------------------------------ #
    # request admission
    # ------------------------------------------------------------------ #
    def _topology(self, g: Graph, cell: ServeCell, backend: str) -> Topology:
        key = (
            cell.name,
            E.topology_hash(g.edge_sources(), g.indices, g.n),
            backend != "jnp",
        )
        return self.cache.get_or_build(
            key, lambda: _pack_topology(g, cell, backend)
        )

    # ------------------------------------------------------------------ #
    # the jitted (cell × batch) programs
    # ------------------------------------------------------------------ #
    def _batched_fn(self, cell: ServeCell, e_blk: int, backend: str):
        sched = self.cfg.schedule or cell.schedule
        key = (cell.name, backend, self.cfg.algo, sched, e_blk)
        fn = self._batched_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg

        def one(w0, is_local, is_ghost, aux, halo, plan):
            state, members, _ = SOL.solve_union_arrays(
                w0, is_local, is_ghost, aux, halo, plan,
                algo=cfg.algo, heavy_k=cfg.heavy_k,
                use_heavy=cfg.use_heavy, sweeps=1_000_000,
                max_rounds=cfg.max_rounds, p=1, schedule=sched,
                backend=backend,
            )
            # the residual weights take over the donated weight plane
            return members, state.w

        plan_axes = None if backend == "jnp" else 0
        batched = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, plan_axes))
        fn = jax.jit(batched, donate_argnums=(0,))
        self._batched_fns[key] = fn
        self.compiles += 1
        return fn

    def _cell_ndev(self, cell: Optional[ServeCell]) -> int:
        """Active device count for a cell's batch axis (cell cap ∧ mesh)."""
        nd = max(1, self._ndev)
        if cell is not None and cell.serve_devices:
            nd = min(nd, cell.serve_devices)
        return nd

    def _sharding(self, nd: int):
        """NamedSharding splitting a leading batch axis over nd devices."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self._meshes.get(nd)
        if mesh is None:
            from repro.launch.mesh import make_serve_mesh

            mesh = make_serve_mesh(nd)
            self._meshes[nd] = mesh
        return NamedSharding(mesh, PartitionSpec("serve"))

    def _batch_size(self, k: int, cell: Optional[ServeCell] = None) -> int:
        """Static batch size for a k-request chunk: the smallest admitted
        bucket, rounded up to a multiple of the active device count so the
        sharded batch axis always splits evenly (a ragged last shard would
        otherwise pay a full recompile for its one-off padded shape)."""
        nd = self._cell_ndev(cell)

        def up(b: int) -> int:
            return ((b + nd - 1) // nd) * nd

        for b in CFG.MWIS_SERVE_BATCH_SIZES:
            if b >= k and b <= self.cfg.max_batch:
                return up(b)
        return up(max(k, min(max(CFG.MWIS_SERVE_BATCH_SIZES),
                             self.cfg.max_batch)))

    # ------------------------------------------------------------------ #
    # solving: pack -> stage (stack + shard/H2D) -> launch -> fetch
    # ------------------------------------------------------------------ #
    def _new_rec(self, cell: ServeCell, backend: str,
                 pipelined: bool) -> dict:
        return dict(cell=cell.name, backend=backend, batch=0, devices=1,
                    pipelined=pipelined, pack_ms=0.0, transfer_ms=0.0,
                    solve_ms=0.0, fetch_ms=0.0)

    def _log_stages(self, rec: dict) -> None:
        self.counters["chunks"] += 1
        if rec["pipelined"]:
            self.counters["pipelined_chunks"] += 1
        for k in ("pack", "transfer", "solve", "fetch"):
            self._stage_totals[k] += rec[k + "_ms"]
        self._stage_log.append(dict(rec))

    def _pack_requests(
        self,
        cell: ServeCell,
        idxs: List[int],
        graphs: List[Graph],
        out: List[Optional[ServeResult]],
        backend: str,
        rec: Optional[dict] = None,
    ) -> Tuple[List[Topology], List[int]]:
        """Per-request host packing with fault isolation; failed requests
        get error results in ``out`` and drop out of the chunk.  With
        ``rec``, its time counts as the chunk's pack stage."""
        topos: List[Topology] = []
        good: List[int] = []
        with span("mwis.serve.pack", rec, "pack_ms"):
            for i in idxs:
                g = graphs[i]
                try:
                    # per-request weight refill on a cached/fresh topology;
                    # a raising pack stays OUT of the cache (get_or_build)
                    topo = self._topology(g, cell, backend)
                    topos.append(Topology(
                        prob=topo.prob._replace(w0=_weight_plane(g, cell)),
                        n=topo.n,
                    ))
                    good.append(i)
                except Exception as e:  # noqa: BLE001 — isolate the request
                    self.counters["pack_errors"] += 1
                    self.events.append(("pack_error", cell.name, str(e)))
                    out[i] = _error_result(g.n, V.REASON_PACK_FAILED,
                                           str(e))
        return topos, good

    def _stage_chunk(
        self, cell: ServeCell, topos: List[Topology], backend: str,
        rec: dict,
    ) -> "_Staged":
        """Stack a chunk to its static batch size on the host and place it:
        the batch axis is padded to a device-count multiple with phantom
        repeat-last instances (results sliced off on fetch) and the chunk
        goes to the device in one ``device_put``, with a ``serve``-mesh
        NamedSharding when more than one device is active.  Stacking on the
        device instead would queue hundreds of small programs behind the
        chunk in flight and hold the host until that chunk finished.  The
        placed weight plane is a fresh device array, the one the launch
        donates."""
        with span("mwis.serve.pack", rec, "pack_ms"):
            args, e_blk, bt = self._stack_chunk(cell, topos, backend)
        nd = self._cell_ndev(cell)
        with span("mwis.serve.stage", rec, "transfer_ms"):
            args = jax.device_put(args,
                                  self._sharding(nd) if nd > 1 else None)
        rec["batch"] = bt
        rec["devices"] = nd
        return _Staged(cell=cell, backend=backend, topos=tuple(topos),
                       args=args, e_blk=e_blk, rec=rec)

    def _stack_chunk(self, cell, topos, backend):
        """The chunk's solve arguments as host arrays stacked to its static
        batch size; returns (args, e_blk, batch size)."""
        k = len(topos)
        bt = self._batch_size(k, cell)
        batch = list(topos) + [topos[-1]] * (bt - k)

        def stack(leaves):
            return jax.tree.map(lambda *xs: np.stack(xs), *leaves)

        probs = [t.prob for t in batch]
        w0s = np.stack([p.w0 for p in probs])
        is_local = stack([p.is_local for p in probs])
        is_ghost = stack([p.is_ghost for p in probs])
        auxs = stack([p.aux for p in probs])
        halos = stack([p.halo for p in probs])
        if backend == "jnp":
            plans = None
            e_blk = 0
        else:
            need = max(p.plan.edge_perm.shape[1] for p in probs)
            hwm = max(self._eblk_hwm.get(cell.name, cell.e_blk), need)
            self._eblk_hwm[cell.name] = hwm
            plans = E.stack_plans([p.plan for p in probs], e_blk=hwm)
            e_blk = hwm
        return (w0s, is_local, is_ghost, auxs, halos, plans), e_blk, bt

    def _launch_chunk(self, staged: "_Staged") -> "_Inflight":
        """Dispatch the jitted vmapped solve; returns without blocking
        (jax dispatch is async — the host is free to pack the next chunk
        while this one runs on the device shards)."""
        fn = self._batched_fn(staged.cell, staged.e_blk, staged.backend)
        # The solve stage is dispatch to ready, across the host work done
        # meanwhile (the next chunk's pack and stage), so its span opens
        # here and closes in _fetch_chunk, or in _run_chunks for a chunk
        # that an escaping error leaves in flight.
        solving = span("mwis.serve.solve", staged.rec, "solve_ms").open()
        try:
            members, _ = fn(*staged.args)
        except BaseException:
            solving.close()
            raise
        return _Inflight(staged=staged, members=members, solving=solving)

    def _fetch_chunk(self, inflight: "_Inflight") -> List[np.ndarray]:
        """Block on the in-flight solve and read back the [n_i] masks."""
        rec = inflight.staged.rec
        try:
            members = inflight.members.block_until_ready()
        finally:
            inflight.solving.close()
        with span("mwis.serve.fetch", rec, "fetch_ms"):
            members = np.asarray(members)
        self._log_stages(rec)
        return [members[i, : t.n]
                for i, t in enumerate(inflight.staged.topos)]

    def _execute_chunk(
        self, cell: ServeCell, topos: List[Topology], backend: str
    ) -> List[np.ndarray]:
        """Solve up to max_batch same-cell topologies; returns [n_i] masks.

        Raises on program build/execute failure — `_solve_chunk` wraps it
        with the fallback chain.  (Tests monkeypatch this seam to inject
        backend failures.)
        """
        rec = self._new_rec(cell, backend, pipelined=False)
        staged = self._stage_chunk(cell, topos, backend, rec)
        return self._fetch_chunk(self._launch_chunk(staged))

    def _solve_chunk(
        self,
        cell: ServeCell,
        idxs: List[int],
        graphs: List[Graph],
        out: List[Optional[ServeResult]],
    ) -> None:
        """Pack + solve one (cell, ≤max_batch) chunk with per-request
        isolation and the backend fallback chain; fills ``out``."""
        while True:
            backend = self._backend
            topos, good = self._pack_requests(cell, idxs, graphs, out,
                                              backend)
            if not good:
                return
            try:
                masks = self._execute_chunk(cell, topos, backend)
            except Exception as e:  # noqa: BLE001 — degrade, don't abort
                chain = FALLBACK_CHAIN[self.cfg.backend]
                pos = chain.index(backend) if backend in chain else len(chain)
                nxt = chain[pos + 1] if pos + 1 < len(chain) else None
                if nxt is None or not self.cfg.fallback:
                    self.counters["solve_errors"] += 1
                    self.events.append(
                        ("backend_failed", cell.name, backend, str(e)))
                    for i in good:
                        out[i] = _error_result(
                            graphs[i].n, V.REASON_BACKEND_FAILED,
                            f"backend {backend!r} failed with no fallback "
                            f"left: {e}")
                    return
                self.counters["fallbacks"] += 1
                self.events.append(("fallback", backend, nxt, str(e)))
                self._backend = nxt
                continue        # retry the chunk on the demoted backend
            for k, i in enumerate(good):
                out[i] = self._finish_result(
                    graphs[i], masks[k], check=(self.cfg.verify == "full")
                    or (self.cfg.verify == "sample" and k == 0))
            return

    # ------------------------------------------------------------------ #
    # the double-buffered chunk pipeline
    # ------------------------------------------------------------------ #
    def _dispatch_chunk(
        self,
        cell: ServeCell,
        idxs: List[int],
        graphs: List[Graph],
        out: List[Optional[ServeResult]],
    ) -> Optional["_Pending"]:
        """Pack + stage + launch one chunk without blocking.  Returns None
        when nothing in the chunk is solvable; a dispatch failure comes
        back as a `_Pending` with ``inflight=None`` — retired by re-running
        the chunk through the synchronous fallback-chain path."""
        backend = self._backend
        rec = self._new_rec(cell, backend, pipelined=True)
        topos, good = self._pack_requests(cell, idxs, graphs, out, backend,
                                          rec)
        if not good:
            return None
        try:
            staged = self._stage_chunk(cell, topos, backend, rec)
            inflight = self._launch_chunk(staged)
        except Exception as e:  # noqa: BLE001 — degrade via the sync path
            self.counters["pipeline_retries"] += 1
            self.events.append(
                ("pipeline_retry", cell.name, backend, str(e)))
            return _Pending(inflight=None, cell=cell, good=good)
        return _Pending(inflight=inflight, cell=cell, good=good)

    def _retire_chunk(
        self,
        pending: "_Pending",
        graphs: List[Graph],
        out: List[Optional[ServeResult]],
    ) -> None:
        """Fetch a dispatched chunk and finish its results; any failure
        (dispatch or in-flight) re-runs the chunk synchronously through
        `_solve_chunk`, which owns the backend fallback chain."""
        if pending.inflight is None:
            self._solve_chunk(pending.cell, pending.good, graphs, out)
            return
        try:
            masks = self._fetch_chunk(pending.inflight)
        except Exception as e:  # noqa: BLE001 — degrade via the sync path
            self.counters["pipeline_retries"] += 1
            self.events.append(
                ("pipeline_retry", pending.cell.name,
                 pending.inflight.staged.backend, str(e)))
            self._solve_chunk(pending.cell, pending.good, graphs, out)
            return
        for k, i in enumerate(pending.good):
            out[i] = self._finish_result(
                graphs[i], masks[k], check=(self.cfg.verify == "full")
                or (self.cfg.verify == "sample" and k == 0))

    def _run_chunks(
        self,
        chunks: List[Tuple[ServeCell, List[int]]],
        graphs: List[Graph],
        out: List[Optional[ServeResult]],
    ) -> None:
        """Run the batch's (cell, idxs) chunks, double-buffered: chunk
        k+1 is packed/staged/launched while chunk k's solve is in flight,
        so host work hides under device time.  Cells opted out of
        pipelining (and single-chunk batches) take the synchronous path —
        results are identical either way, only the overlap differs."""
        t_wall = time.perf_counter()
        pipe = self.cfg.pipeline and len(chunks) > 1
        pending: Optional[_Pending] = None
        try:
            for cell, idxs in chunks:
                if not (pipe and cell.pipeline):
                    if pending is not None:
                        self._retire_chunk(pending, graphs, out)
                        pending = None
                    self._solve_chunk(cell, idxs, graphs, out)
                    continue
                nxt = self._dispatch_chunk(cell, idxs, graphs, out)
                if pending is not None:
                    self._retire_chunk(pending, graphs, out)
                pending = nxt
            if pending is not None:
                self._retire_chunk(pending, graphs, out)
        finally:
            if pending is not None and pending.inflight is not None:
                pending.inflight.solving.close()
        self._wall_s += time.perf_counter() - t_wall

    def _solve_staged_one(self, g: Graph, cell: ServeCell) -> ServeResult:
        """One instance through the shape-descent path
        (:func:`repro.core.solvers.solve_staged`): enter at ``cell``'s
        shape, shrink onto smaller serve cells as reduction collapses the
        kernel.  Descent plans go through the shared :class:`PlanCache`
        (counted in ``cache_descent_*``).  Same isolation contract as the
        batched path: never raises, walks the backend fallback chain."""
        cfg = self.cfg
        sched = cfg.schedule or cell.schedule
        while True:
            backend = self._backend
            dcfg = SOL.DisReduConfig(
                heavy_k=cfg.heavy_k, use_heavy=cfg.use_heavy, mode="sync",
                max_rounds=cfg.max_rounds, schedule=sched, backend=backend,
                r_blk=None if backend == "jnp" else cell.r_blk,
                descent=True, descent_every=cfg.descent_every,
            )
            try:
                members, st = SOL.solve_staged(
                    g, 1, cfg.algo, dcfg, plan_cache=self.cache,
                    pad_to=dict(L=cell.L, G=cell.G, E=cell.E, B=cell.B,
                                S=cell.S),
                    window_cap=cell.D, common_cap=cell.Dc,
                )
            except Exception as e:  # noqa: BLE001 — degrade, don't abort
                chain = FALLBACK_CHAIN[self.cfg.backend]
                pos = chain.index(backend) if backend in chain else len(chain)
                nxt = chain[pos + 1] if pos + 1 < len(chain) else None
                if nxt is None or not self.cfg.fallback:
                    self.counters["solve_errors"] += 1
                    self.events.append(
                        ("backend_failed", cell.name, backend, str(e)))
                    return _error_result(
                        g.n, V.REASON_BACKEND_FAILED,
                        f"backend {backend!r} failed with no fallback "
                        f"left: {e}")
                self.counters["fallbacks"] += 1
                self.events.append(("fallback", backend, nxt, str(e)))
                self._backend = nxt
                continue
            self.counters["descent_solves"] += 1
            self.counters["descents"] += int(st["descents"])
            return self._finish_result(
                g, members, check=self.cfg.verify in ("sample", "full"))

    def _finish_result(
        self, g: Graph, mask: np.ndarray, check: bool
    ) -> ServeResult:
        with span("mwis.serve.verify"):
            weight = int(g.weights[mask].sum(dtype=np.int64))
            if check:
                self.counters["verify_checked"] += 1
                rep = V.verify_result(g, mask, weight)
                if not rep.ok:
                    self.counters["verify_failures"] += 1
                    self.events.append(("verify_failure", rep.detail))
                    return ServeResult(
                        members=mask, weight=weight, ok=False,
                        reason=rep.reason,
                        error=f"{rep.reason}: {rep.detail}",
                    )
            return ServeResult(members=mask, weight=weight)

    def solve_batch(self, graphs: Sequence[Graph]) -> List[ServeResult]:
        """Solve many instances; results in request order.

        Never raises for a bad request: malformed/oversize/unpackable
        instances come back as ``ok=False`` results with stable reason
        codes while the rest of the batch solves normally.
        """
        order: Dict[str, List[int]] = {}
        staged: List[Tuple[int, ServeCell]] = []
        cells_by_name = {c.name: c for c in self.cells}
        admitted: List[Graph] = list(graphs)
        out: List[Optional[ServeResult]] = [None] * len(graphs)
        for i, g in enumerate(graphs):
            self.counters["requests"] += 1
            if self.cfg.validate:
                fixed, rep = V.canonicalize(g)
                if not rep.ok:
                    self.counters["rejected"] += 1
                    self.events.append(("rejected", rep.reason, rep.detail))
                    try:
                        n_bad = int(g.n)
                    except Exception:  # noqa: BLE001 — malformed input
                        n_bad = 0
                    out[i] = _error_result(n_bad, rep.reason, rep.detail)
                    continue
                if rep.repairs:
                    self.counters["repaired"] += 1
                    self.events.append(("repaired", rep.repairs))
                admitted[i] = g = fixed
            if g.n == 0:    # trivially solved; skip the device entirely
                out[i] = ServeResult(members=np.zeros(0, bool), weight=0)
                continue
            try:
                cell = bucket_for(g.n, g.num_directed_edges, self.cells)
            except ValueError as e:
                # oversize for every serve cell — with descent on, admit
                # through a kind="descent" entry shape (staged path only)
                dcell = None
                if self.descent_cells:
                    try:
                        dcell = bucket_for(g.n, g.num_directed_edges,
                                           self.descent_cells)
                    except ValueError:
                        dcell = None
                if dcell is None:
                    self.counters["rejected"] += 1
                    self.events.append(
                        ("rejected", V.REASON_OVERSIZE, str(e)))
                    out[i] = _error_result(g.n, V.REASON_OVERSIZE, str(e))
                    continue
                self.counters["oversize_admitted"] += 1
                staged.append((i, dcell))
                continue
            if (self.cfg.descent == "auto"
                    and cell.L >= self.cfg.descent_min_L):
                staged.append((i, cell))
            else:
                order.setdefault(cell.name, []).append(i)

        chunks: List[Tuple[ServeCell, List[int]]] = []
        for cell_name, idxs in order.items():
            cell = cells_by_name[cell_name]
            for c0 in range(0, len(idxs), self.cfg.max_batch):
                chunks.append((cell, idxs[c0 : c0 + self.cfg.max_batch]))
        self._run_chunks(chunks, admitted, out)
        for i, cell in staged:
            out[i] = self._solve_staged_one(admitted[i], cell)
        return out  # type: ignore[return-value]

    def solve_one(self, g: Graph) -> ServeResult:
        return self.solve_batch([g])[0]

    @property
    def stats(self) -> dict:
        s = self.cache.stats
        stage_ms = {k: round(v, 3) for k, v in self._stage_totals.items()}
        p50 = {}
        for k in ("pack", "transfer", "solve", "fetch"):
            vals = [r[k + "_ms"] for r in self._stage_log]
            p50[k] = round(float(np.median(vals)), 3) if vals else 0.0
        busy_ms = sum(self._stage_totals.values())
        wall_ms = self._wall_s * 1e3
        # fraction of summed stage time hidden under other chunks' device
        # time — 0.0 when serial (wall >= busy), higher when pipelined
        overlap = (max(0.0, 1.0 - wall_ms / busy_ms) if busy_ms > 0
                   else 0.0)
        return dict(
            cache_hits=s.hits, cache_misses=s.misses,
            cache_evictions=s.evictions, cache_size=s.size,
            cache_errors=s.errors,
            cache_descent_hits=s.descent_hits,
            cache_descent_misses=s.descent_misses,
            programs=len(self._batched_fns), compiles=self.compiles,
            e_blk_hwm=dict(self._eblk_hwm),
            backend=self.cfg.backend, backend_active=self._backend,
            devices=max(1, self._ndev),
            pipeline=self.cfg.pipeline,
            stage_ms=stage_ms,
            stage_p50_ms=p50,
            wall_ms=round(wall_ms, 3),
            overlap_ratio=round(overlap, 4),
            **self.counters,
        )


# --------------------------------------------------------------------- #
# sustained-throughput measurement (benchmarks/serve_bench.py + CLI)
# --------------------------------------------------------------------- #
def measure_throughput(
    service: MWISService,
    batches: Sequence[Sequence[Graph]],
    *,
    warmup: int = 1,
) -> dict:
    """Drive pre-built request batches through a service; returns
    instances/sec + per-batch latency percentiles (ms).

    ``warmup`` counts full passes over the batch list before timing, so
    every (cell × batch-bucket) program is compiled (and every topology
    cached) before the measured pass — the steady serving state.
    """
    for _ in range(warmup):
        for b in batches:
            service.solve_batch(list(b))
    lat = []
    n_inst = 0
    t0 = time.perf_counter()
    for b in batches:
        t1 = time.perf_counter()
        service.solve_batch(list(b))
        lat.append((time.perf_counter() - t1) * 1e3)
        n_inst += len(b)
    wall = time.perf_counter() - t0
    lat_a = np.asarray(lat)
    return dict(
        instances=n_inst,
        instances_per_sec=round(n_inst / wall, 1),
        p50_ms=round(float(np.percentile(lat_a, 50)), 3),
        p99_ms=round(float(np.percentile(lat_a, 99)), 3),
        batches=len(batches),
    )
