"""Public segment-reduction API with host-side CSR→blocked-ELL packing and
pallas/jnp dispatch."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode, use_pallas
from repro.kernels.segment_coo.kernel import (
    segment_fused_planar, segment_sum_blocked,
)
from repro.kernels.segment_coo.ref import (
    segment_fused_blocked_ref, segment_sum_blocked_ref,
)


def pack_blocks(
    row: np.ndarray, n_rows: int, *, r_blk: int = 8, e_blk_multiple: int = 1,
    edges: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host packing: row-sorted edge ids → (edge_perm [n_blocks, E_BLK],
    lrow [n_blocks, E_BLK]).  edge_perm indexes the original edge array;
    padding slots point at edge 0 with lrow = r_blk (ignored) — so the edge
    array must be non-empty (the partitioned graphs always pad E ≥ 1).
    ``e_blk_multiple`` rounds the edge budget up (sublane alignment);
    ``edges`` (ids into ``row``) packs only those edges (default: all)."""
    ids = np.arange(row.shape[0]) if edges is None else np.asarray(edges)
    order = ids[np.argsort(row[ids], kind="stable")]
    rs = row[order]
    n_blocks = (n_rows + r_blk - 1) // r_blk
    blk_of_edge = rs // r_blk
    counts = np.bincount(blk_of_edge, minlength=n_blocks)
    e_blk = max(int(counts.max(initial=1)), 1)
    e_blk = ((e_blk + e_blk_multiple - 1) // e_blk_multiple) * e_blk_multiple
    edge_perm = np.zeros((n_blocks, e_blk), dtype=np.int64)
    lrow = np.full((n_blocks, e_blk), r_blk, dtype=np.int32)
    starts = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for b in range(n_blocks):
        sl = slice(starts[b], starts[b + 1])
        k = starts[b + 1] - starts[b]
        edge_perm[b, :k] = order[sl]
        lrow[b, :k] = rs[sl] - b * r_blk
    return edge_perm, lrow, e_blk


def pack_blocks_stacked(
    rows: np.ndarray, n_rows: int, *, r_blk: int = 8, e_blk_multiple: int = 1,
    edges: Optional[Sequence[np.ndarray]] = None,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Stacked packing for the shard_map path: rows is [p, E]; every PE is
    packed against the same n_rows and padded to a SHARED E_BLK (max over
    PEs) so the per-PE plan arrays stack into one [p, n_blocks, E_BLK]
    mesh-sharded input.  ``edges[i]`` selects PE i's packed edges."""
    p = rows.shape[0]
    packed = [
        pack_blocks(rows[i], n_rows, r_blk=r_blk,
                    e_blk_multiple=e_blk_multiple,
                    edges=None if edges is None else edges[i])
        for i in range(p)
    ]
    e_blk = max(pb[2] for pb in packed)
    n_blocks = packed[0][0].shape[0]
    edge_perm = np.zeros((p, n_blocks, e_blk), dtype=np.int64)
    lrow = np.full((p, n_blocks, e_blk), r_blk, dtype=np.int32)
    for i, (perm_i, lrow_i, eb_i) in enumerate(packed):
        edge_perm[i, :, :eb_i] = perm_i
        lrow[i, :, :eb_i] = lrow_i
    return edge_perm, lrow, e_blk


def segment_sum_coo(
    data: jax.Array,        # [E, D] edge payloads (original edge order)
    edge_perm: jax.Array,   # [n_blocks, E_BLK] from pack_blocks
    lrow: jax.Array,        # [n_blocks, E_BLK]
    n_rows: int,
    *,
    r_blk: int = 8,
    force_pallas: bool | None = None,
) -> jax.Array:
    """Blocked segment sum; returns [n_rows, D]."""
    n_blocks = edge_perm.shape[0]
    blocked = data[edge_perm.reshape(-1)].reshape(
        n_blocks, edge_perm.shape[1], data.shape[-1]
    )
    enable = use_pallas() if force_pallas is None else force_pallas
    if enable:
        out = segment_sum_blocked(
            blocked, lrow, r_blk=r_blk, interpret=interpret_mode()
        )
    else:
        out = segment_sum_blocked_ref(blocked, lrow, r_blk=r_blk)
    return out.reshape(n_blocks * r_blk, -1)[:n_rows]


def segment_fused_coo(
    edge_perm: jax.Array,   # [n_blocks, E_BLK] from pack_blocks
    lrow: jax.Array,        # [n_blocks, E_BLK]
    n_rows: int,
    *,
    data_sum: jax.Array | None = None,   # [E, Ds] edge payloads to sum
    data_max: jax.Array | None = None,   # [E, Dm] edge payloads to max
    data_min: jax.Array | None = None,   # [E, Dn] edge payloads to min
    data_or: jax.Array | None = None,    # [E, Do] edge payloads to bitwise-OR
    or_nbits: int = 16,                  # bit width of the OR payloads
    r_blk: int = 8,
    force_pallas: bool | None = None,
):
    """Fused blocked segment sum+max+min+or over one packed edge list;
    returns a (sum, max, min, or) tuple of [n_rows, D*] arrays (None where
    the payload group is absent).  All payload groups share the single
    gather of the blocked edge permutation — the engine's
    one-pass-per-sweep contract."""
    if all(d is None for d in (data_sum, data_max, data_min, data_or)):
        raise ValueError("segment_fused_coo needs at least one payload")
    n_blocks, e_blk = edge_perm.shape
    groups = (data_sum, data_max, data_min, data_or)
    enable = use_pallas() if force_pallas is None else force_pallas
    if enable:
        # payload-major gather straight into the kernel's [D, nb, E_BLK]
        # layout (edges on lanes)
        outs = segment_fused_planar(
            *(None if d is None else d.T[:, edge_perm] for d in groups[:3]),
            lrow, r_blk=r_blk, or_nbits=or_nbits,
            data_or=None if data_or is None else data_or.T[:, edge_perm],
            interpret=interpret_mode(),
        )
        return tuple(
            o.reshape(o.shape[0], n_blocks * r_blk)[:, :n_rows].T
            if o is not None else None
            for o in outs
        )
    bsum, bmax, bmin, bor = (
        None if d is None else d[edge_perm.reshape(-1)].reshape(
            n_blocks, e_blk, d.shape[-1])
        for d in groups
    )
    outs = segment_fused_blocked_ref(
        bsum, bmax, bmin, lrow, data_or=bor, or_nbits=or_nbits, r_blk=r_blk,
    )
    return tuple(
        o.reshape(n_blocks * r_blk, -1)[:n_rows] if o is not None else None
        for o in outs
    )
