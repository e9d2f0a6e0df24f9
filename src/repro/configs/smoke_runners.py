"""Reduced-config smoke runners: instantiate a small config of the same
family and run one forward/train step on CPU, asserting output shapes and
finiteness.  Full configs are exercised only via the dry-run."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as MC
from repro.train import optimizer as opt


def _assert_finite(tree, what=""):
    for leaf in jax.tree.leaves(tree):
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f":
            assert np.isfinite(arr).all(), f"non-finite values in {what}"


def lm_smoke(cfg):
    from repro.models import transformer as T

    specs = T.param_specs(cfg)
    params = MC.init_params(specs, jax.random.key(0))
    B, S = 2, 32
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab),
    }
    ostate = opt.adamw_init(params)
    ocfg = opt.AdamWConfig()

    @jax.jit
    def step(params, ostate, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg)
        )(params)
        params, ostate = opt.adamw_update(grads, ostate, params, ocfg)
        return loss, params, ostate

    loss, params2, _ = step(params, ostate, batch)
    assert np.isfinite(float(loss)), "train loss must be finite"
    _assert_finite(params2, f"{cfg.name} params after update")

    # decode step against a KV cache
    (kc_abs, vc_abs), _ = T.make_kv_cache_specs(cfg, B, 64)
    kc = jnp.zeros(kc_abs.shape, kc_abs.dtype)
    vc = jnp.zeros(vc_abs.shape, vc_abs.dtype)

    @jax.jit
    def decode(params, kc, vc, tok, pos):
        return T.serve_step(params, (kc, vc), tok, pos, cfg)

    logits, (kc, vc) = decode(
        params, kc, vc,
        jnp.zeros((B, 1), jnp.int32), jnp.asarray(3, jnp.int32),
    )
    assert logits.shape == (B, cfg.vocab)
    _assert_finite(logits, f"{cfg.name} decode logits")


def gnn_smoke(module, cfg, *, molecular: bool, sampled: bool = False):
    from repro.graphs import generators as gen
    from repro.graphs.sampler import sample_fanout, build_triplets

    rng = np.random.default_rng(0)
    g = gen.rgg2d(120, avg_deg=6, seed=0)
    if sampled:
        sub = sample_fanout(
            g, np.arange(8), cfg.sample_sizes, rng=rng,
            pad_nodes=160, pad_edges=400,
        )
        row, col = sub.row, sub.col
        n = sub.n_sub
    else:
        src = g.edge_sources()
        row = src.astype(np.int32)
        col = g.indices.astype(np.int32)
        n = g.n
    d_feat = getattr(cfg, "d_feat", 16)
    batch = dict(
        node_feat=jnp.asarray(rng.normal(size=(n, d_feat)), jnp.float32),
        row=jnp.asarray(row), col=jnp.asarray(col),
        labels=jnp.asarray(rng.integers(0, 4, size=n), jnp.int32),
        label_mask=jnp.ones((n,), jnp.float32),
    )
    if molecular:
        tri = build_triplets(np.asarray(row), np.asarray(col), n,
                             budget=4 * row.shape[0])
        batch.update(
            pos=jnp.asarray(rng.normal(size=(n, 3)), jnp.float32),
            batch_id=jnp.zeros((n,), jnp.int32),
            energy=jnp.zeros((1,), jnp.float32),
            triplets=jnp.asarray(tri),
        )
    specs = module.param_specs(cfg)
    params = MC.init_params(specs, jax.random.key(0))
    ostate = opt.adamw_init(params)
    ocfg = opt.AdamWConfig()

    @jax.jit
    def step(params, ostate, batch):
        if molecular:
            batch = dict(batch, n_graphs=1)  # static
        loss, grads = jax.value_and_grad(
            lambda p: module.loss_fn(p, batch, cfg)
        )(params)
        params, ostate = opt.adamw_update(grads, ostate, params, ocfg)
        return loss, params, ostate

    loss, params2, _ = step(params, ostate, batch)
    assert np.isfinite(float(loss)), "gnn loss must be finite"
    _assert_finite(params2, "gnn params after update")


def dlrm_smoke(cfg):
    from repro.models import dlrm as M

    specs = M.param_specs(cfg)
    params = MC.init_params(specs, jax.random.key(0))
    rng = np.random.default_rng(0)
    B = 16
    batch = dict(
        dense=jnp.asarray(rng.normal(size=(B, cfg.n_dense)), jnp.float32),
        sparse=jnp.asarray(
            rng.integers(0, 3, size=(B, cfg.n_sparse)), jnp.int32
        ),
        labels=jnp.asarray(rng.integers(0, 2, size=B), jnp.int32),
    )
    ostate = opt.adamw_init(params)
    ocfg = opt.AdamWConfig()

    @jax.jit
    def step(params, ostate, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, cfg)
        )(params)
        params, ostate = opt.adamw_update(grads, ostate, params, ocfg)
        return loss, params, ostate

    loss, params2, _ = step(params, ostate, batch)
    assert np.isfinite(float(loss))
    _assert_finite(params2, "dlrm params")

    probs = jax.jit(lambda p, b: M.serve_step(p, b, cfg))(
        params, {k: batch[k] for k in ("dense", "sparse")}
    )
    assert probs.shape == (B,)
    # retrieval scoring
    rb = dict(
        dense=batch["dense"][:1],
        candidates=jnp.asarray(
            rng.integers(0, cfg.vocabs[0], size=(1, 64)), jnp.int32
        ),
    )
    scores = jax.jit(lambda p, b: M.retrieval_step(p, b, cfg))(params, rb)
    assert scores.shape == (64,)


def mwis_smoke():
    """Reduced end-to-end MWIS: partition → DisReduA → RnP → verify."""
    from repro.core import partition as part, solvers as S
    from repro.core.distributed import DisReduConfig
    from repro.graphs import generators as gen

    g = gen.rgg2d(200, avg_deg=6, seed=0)
    pg = part.partition_graph(g, 4, window_cap=8)
    members, _, _ = S.solve(pg, "rnp", DisReduConfig(heavy_k=6, mode="async"))
    assert g.is_independent_set(members)
    assert g.set_weight(members) > 0
