"""Fused EmbeddingBag kernel — DLRM's lookup hot path.

out[b] = Σ_k weight[b, k] · table[idx[b, k]]   (sum-mode bag)

JAX has no native EmbeddingBag; the jnp form is gather → multiply →
segment-sum, three HBM round-trips of the [B·K, dim] gathered matrix.  The
kernel fuses them: bags are tiled to [B_BLK, dim] output tiles; the table
stays in HBM (ANY memory space) and rows are read on demand through
dynamic ref slices (``table_ref[pl.ds(row, 1), :]``), accumulating in a
VMEM tile.  dim = 128 is one
lane tile — MXU/VPU aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bag_kernel(idx_ref, wgt_ref, table_ref, out_ref, *, k_bag: int):
    b_blk = out_ref.shape[1]

    def body(b, _):
        def inner(j, acc):
            row = idx_ref[0, b, j]
            vec = table_ref[pl.ds(row, 1), :][0].astype(jnp.float32)
            return acc + vec * wgt_ref[0, b, j].astype(jnp.float32)

        acc = jax.lax.fori_loop(
            0, k_bag, inner,
            jnp.zeros((out_ref.shape[2],), jnp.float32),
        )
        out_ref[0, b, :] = acc.astype(out_ref.dtype)
        return _

    jax.lax.fori_loop(0, b_blk, body, 0)


@functools.partial(jax.jit, static_argnames=("b_blk", "interpret"))
def embedding_bag_fused(
    table: jax.Array,   # [V, D]
    idx: jax.Array,     # [B, K] int32
    wgt: jax.Array,     # [B, K] f32 per-sample weights
    *,
    b_blk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    B, K = idx.shape
    V, D = table.shape
    nb = (B + b_blk - 1) // b_blk
    pad = nb * b_blk - B
    if pad:
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        wgt = jnp.pad(wgt, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_bag_kernel, k_bag=K),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, b_blk, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b_blk, K), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # table stays in HBM
        ],
        out_specs=pl.BlockSpec((1, b_blk, D), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, b_blk, D), table.dtype),
        interpret=interpret,
    )(idx.reshape(nb, b_blk, K), wgt.reshape(nb, b_blk, K), table)
    return out.reshape(nb * b_blk, D)[:B]
