"""Row-blocked segment-reduction kernels (the SpMM/message-passing primitive).

Layout: the host packs row-sorted COO edges into ``n_blocks`` row blocks of
``R_BLK`` output rows each; every block's edge range is padded to a fixed
``E_BLK`` budget (blocked-ELL).  Grid = (n_blocks,).

Per grid step, VMEM holds:
  data  [E_BLK, D]   gathered edge payloads,
  lrow  [E_BLK, 1]   row index *within* the block (R_BLK for padding),
  out   [R_BLK, D]   accumulator tile.

TPU adaptation: the scatter-accumulate is expressed as a one-hot matmul
(``onehot[lrow] @ data``) so it runs on the MXU instead of serialized
dynamic-update-slices — the standard TPU trick for small-radix scatters.
D should be lane-aligned (×128) and R_BLK sublane-aligned (×8) for full
MXU utilization.

Two entry points:

  * ``segment_sum_blocked``   — the original sum-only kernel (float payloads;
    message passing / embedding reductions),
  * ``segment_fused_planar`` — fused multi-payload sum + max + min +
    bitwise-OR in ONE pass over the packed edge blocks, on a payload-major
    ``[D, n_blocks, E_BLK]`` layout (edges on lanes; eight row blocks per
    grid step).  This is the aggregate-engine hot path
    (:mod:`repro.core.engine`): one sweep of the MWIS reduction rules needs
    neighborhood sums (S, deg), maxes (M, argmax-id) AND the capped-window
    activity/clique bitmasks over the same masked edge list, so reading the
    blocked payloads once and producing all reductions amortizes the HBM
    traffic that dominates this memory-bound op.  Sums and ORs share one
    int8 one-hot MXU matmul with int32 accumulation (the v5e MXU takes no
    int32 operands): a sum column enters as eight unsigned 4-bit limbs
    recombined by shifts, an OR column as 0/1 bitplanes (OR == "count per
    bit > 0").  Max/min use masked VPU lane reductions (max has no matmul
    form).  Results are bit-identical to ``jax.ops.segment_{sum,max,min}``
    (sums wrap mod 2**32 alike) / a per-segment ``np.bitwise_or``
    regardless of edge order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _seg_kernel(data_ref, lrow_ref, out_ref, *, r_blk: int):
    data = data_ref[0]                         # [E_BLK, D]
    lrow = lrow_ref[0][:, 0]                   # [E_BLK]
    onehot = (
        lrow[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, r_blk), 1)
    ).astype(data.dtype)                       # [E_BLK, R_BLK]
    out_ref[0] = jax.lax.dot_general(
        onehot, data,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r_blk", "interpret"))
def segment_sum_blocked(
    data: jax.Array,    # [n_blocks, E_BLK, D]
    lrow: jax.Array,    # [n_blocks, E_BLK] int32 (R_BLK = padding)
    *,
    r_blk: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns [n_blocks, R_BLK, D]; caller reshapes to [n_rows, D]."""
    n_blocks, e_blk, d = data.shape
    # widen the padding row into an extra one-hot column? no: padding rows
    # (lrow == R_BLK) match no iota column and contribute nowhere.
    out = pl.pallas_call(
        functools.partial(_seg_kernel, r_blk=r_blk),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, e_blk, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, e_blk, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r_blk, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, r_blk, d), data.dtype),
        interpret=interpret,
    )(data, lrow[..., None])
    return out


# --------------------------------------------------------------------- #
# fused multi-payload sum/max/min/or
# --------------------------------------------------------------------- #
#: Row blocks per grid step: one sublane tile of the [n_blocks, E_BLK] lrow
#: array, so every block shape is (8, 128)-legal whatever E_BLK is.
BLOCKS_PER_STEP = 8

#: Sum payloads ride the MXU as eight unsigned 4-bit limbs per int32 column.
_SUM_LIMBS, _SUM_LIMB_BITS = 8, 4


def _identity(dtype, kind: str):
    """Reduction identities matching jax.ops.segment_* empty-segment init."""
    info = jnp.iinfo(dtype)
    return {"max": info.min, "min": info.max}[kind]


def _planes(x, n: int, width: int):
    """[1, E] int32 -> [n, E] unsigned limbs ``(x >>> width*k) & mask``."""
    shifts = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) * width
    x = jnp.broadcast_to(x, (n, x.shape[1]))
    return jax.lax.shift_right_logical(x, shifts) & ((1 << width) - 1)


def _recombine(cnt, width: int):
    """[n, R] per-limb sums -> [1, R] ``sum_k cnt_k << width*k`` (wraps
    mod 2**32 exactly like an int32 segment_sum)."""
    shifts = jax.lax.broadcasted_iota(jnp.int32, (cnt.shape[0], 1), 0) * width
    return jnp.sum(cnt << shifts, axis=0, keepdims=True)


def _seg_fused_kernel(*refs, r_blk: int, bb: int, or_planes: int,
                      has_sum: bool, has_max: bool, has_min: bool,
                      has_or: bool):
    refs = list(refs)
    lrow_ref = refs.pop(0)                           # [bb, E_BLK]
    dsum = refs.pop(0) if has_sum else None          # [Ds, bb, E_BLK]
    dmax = refs.pop(0) if has_max else None          # [Dm, bb, E_BLK]
    dmin = refs.pop(0) if has_min else None          # [Dn, bb, E_BLK]
    dor = refs.pop(0) if has_or else None            # [Do, bb, E_BLK]
    osum = refs.pop(0) if has_sum else None          # [Ds, bb, R_BLK]
    omax = refs.pop(0) if has_max else None
    omin = refs.pop(0) if has_min else None
    oor = refs.pop(0) if has_or else None
    e_blk = lrow_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (r_blk, e_blk), 0)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (r_blk, r_blk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (r_blk, r_blk), 1))
    for b in range(bb):
        hit = rows == lrow_ref[b : b + 1, :]         # [R_BLK, E_BLK]
        # sums and ORs: one int8 one-hot matmul over 0..15 limbs and 0/1
        # bitplanes, int32 accumulation (exact: E_BLK * 15 < 2**31)
        planes = []
        if has_sum:
            planes += [_planes(dsum[d, b : b + 1, :], _SUM_LIMBS,
                               _SUM_LIMB_BITS) for d in range(dsum.shape[0])]
        if has_or:
            planes += [_planes(dor[c, b : b + 1, :], or_planes, 1)
                       for c in range(dor.shape[0])]
        if planes:
            cnt = jax.lax.dot_general(
                jnp.concatenate(planes, axis=0).astype(jnp.int8),
                hit.astype(jnp.int8),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )                                        # [K, R_BLK]
            k = 0
            for d in range(dsum.shape[0] if has_sum else 0):
                osum[d, b : b + 1, :] = _recombine(
                    cnt[k : k + _SUM_LIMBS], _SUM_LIMB_BITS)
                k += _SUM_LIMBS
            for c in range(dor.shape[0] if has_or else 0):
                oor[c, b : b + 1, :] = _recombine(
                    (cnt[k : k + or_planes] > 0).astype(jnp.int32), 1)
                k += or_planes
        # max/min have no matmul form: masked lane reduction per row gives
        # an [R_BLK, 1] column, turned into an [1, R_BLK] row through the
        # diagonal (a sublane reduction, no transpose)
        for data, out, kind in ((dmax, omax, "max"), (dmin, omin, "min")):
            if data is None:
                continue
            ident = _identity(data.dtype, kind)
            red = jnp.max if kind == "max" else jnp.min
            for d in range(data.shape[0]):
                col = red(jnp.where(hit, data[d, b : b + 1, :], ident),
                          axis=1, keepdims=True)
                out[d, b : b + 1, :] = red(jnp.where(eye, col, ident),
                                           axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("r_blk", "or_nbits", "interpret")
)
def segment_fused_planar(
    data_sum: jax.Array | None,   # [Ds, n_blocks, E_BLK] i32 or None
    data_max: jax.Array | None,   # [Dm, n_blocks, E_BLK] i32 or None
    data_min: jax.Array | None,   # [Dn, n_blocks, E_BLK] i32 or None
    lrow: jax.Array,              # [n_blocks, E_BLK] i32 (R_BLK = padding)
    *,
    r_blk: int,
    data_or: jax.Array | None = None,  # [Do, n_blocks, E_BLK] i32, values
                                       # in [0, 2**or_nbits)
    or_nbits: int = 16,
    interpret: bool = False,
):
    """The fused kernel on its native payload-major layout: the edge axis
    sits on lanes, so narrow payload groups cost no lane padding in HBM.
    Returns (sum, max, min, or) of shape [D*, n_blocks, R_BLK] (None for
    absent groups)."""
    if not 0 < or_nbits < 32:
        raise ValueError(f"or_nbits must be in (0, 32), got {or_nbits}")
    groups = (data_sum, data_max, data_min, data_or)
    payloads = [p for p in groups if p is not None]
    if not payloads:
        raise ValueError("segment_fused_planar needs at least one payload")
    if any(p.dtype != jnp.int32 for p in payloads):
        raise ValueError("segment_fused_planar takes int32 payloads only")
    n_blocks, e_blk = lrow.shape
    bb = min(BLOCKS_PER_STEP, n_blocks)
    pad = -n_blocks % bb
    if pad:
        lrow = jnp.pad(lrow, ((0, pad), (0, 0)), constant_values=r_blk)
        payloads = [jnp.pad(p, ((0, 0), (0, pad), (0, 0))) for p in payloads]
    nbp = n_blocks + pad
    in_specs = [pl.BlockSpec((bb, e_blk), lambda i: (i, 0))]
    out_specs, out_shapes = [], []
    for p in payloads:
        in_specs.append(pl.BlockSpec((p.shape[0], bb, e_blk),
                                     lambda i: (0, i, 0)))
        out_specs.append(pl.BlockSpec((p.shape[0], bb, r_blk),
                                      lambda i: (0, i, 0)))
        out_shapes.append(
            jax.ShapeDtypeStruct((p.shape[0], nbp, r_blk), jnp.int32))
    outs = pl.pallas_call(
        functools.partial(
            _seg_fused_kernel, r_blk=r_blk, bb=bb,
            or_planes=-(-or_nbits // 8) * 8,
            has_sum=data_sum is not None, has_max=data_max is not None,
            has_min=data_min is not None, has_or=data_or is not None,
        ),
        grid=(nbp // bb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(lrow, *payloads)
    outs = [o[:, :n_blocks] for o in outs]
    return tuple(outs.pop(0) if p is not None else None for p in groups)

