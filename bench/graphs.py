"""The benchmark's own copies of the system's input generators.

``gnm`` is a uniform G(n, m), KaGen's GNM model: ``m`` distinct edges,
every m-subset of the vertex pairs equally likely, then vertex weights
uniform in [1, 200] (arXiv:2510.13306 Table C.1).  ``serve_stream`` is the
serving launcher's instance stream (``launch/serve.mwis_requests``), copied
draw for draw with the system's own G(n, m) stand-in
(``graphs/generators.gnm``, here ``launcher_gnm``): the serve cells cycled
per topology, a topology on ``n_frac`` of the cell's vertices with
``m = min(2n, E/4)``, each repeated with fresh weights.

A graph is returned as symmetric CSR arrays ``(indptr, indices, weights)``
with rows sorted ascending, as the system's ``Graph`` holds it.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

Csr = Tuple[np.ndarray, np.ndarray, np.ndarray]


def rng_of(*keys: int) -> np.random.Generator:
    """A generator keyed by whole numbers of any size or sign."""
    return np.random.default_rng([int(k) % (1 << 64) for k in keys])


def csr(n: int, pairs: np.ndarray, weights: np.ndarray) -> Csr:
    """Symmetric CSR of distinct undirected ``pairs``."""
    src = np.concatenate([pairs[:, 0], pairs[:, 1]])
    dst = np.concatenate([pairs[:, 1], pairs[:, 0]])
    order = np.lexsort((dst, src))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst[order].astype(np.int32), weights.astype(np.int32)


def distinct_pairs(rng: np.random.Generator, n: int, m: int) -> np.ndarray:
    """At least ``m`` distinct (lower, higher) vertex pairs, sorted: draws of
    uniform pairs, deduplicated, until there are enough."""
    pairs = np.zeros((0, 2), dtype=np.int64)
    attempts = 0
    while pairs.shape[0] < m and attempts < 64:
        k = int((m - pairs.shape[0]) * 1.4) + 16
        u = rng.integers(0, n, size=k, dtype=np.int64)
        v = rng.integers(0, n, size=k, dtype=np.int64)
        keep = u != v
        cand = np.stack([np.minimum(u[keep], v[keep]),
                         np.maximum(u[keep], v[keep])], axis=1)
        pairs = np.unique(np.concatenate([pairs, cand], axis=0), axis=0)
        attempts += 1
    return pairs


def gnm(n: int, m: int, seed: int, lo: int = 1, hi: int = 200) -> Csr:
    """Uniform G(n, m): a uniformly random m-subset of distinct pairs."""
    rng = np.random.default_rng(seed)
    pairs = distinct_pairs(rng, n, m)
    pairs = pairs[np.sort(rng.permutation(pairs.shape[0])[:m])]
    weights = rng.integers(lo, hi + 1, size=n, dtype=np.int32)
    return csr(n, pairs, weights)


def launcher_gnm(n: int, m: int, seed: int, lo: int = 1,
                 hi: int = 200) -> Csr:
    """The system's G(n, m) stand-in, draw for draw: the first ``m`` of the
    sorted distinct pairs, so vertices of high id lose edges."""
    rng = np.random.default_rng(seed)
    pairs = distinct_pairs(rng, n, m)
    weights = rng.integers(lo, hi + 1, size=n, dtype=np.int32)
    return csr(n, pairs[:m], weights)


def serve_stream(cells: List[dict], n_requests: int, repeat: int,
                 n_frac: float, seed: int, lo: int = 1,
                 hi: int = 200) -> List[Csr]:
    """``n_requests`` instances: one topology per cell in turn, each sent
    ``repeat`` times with fresh weights."""
    rng = np.random.default_rng(seed)
    reqs: List[Csr] = []
    topo = 0
    while len(reqs) < n_requests:
        cell = cells[topo % len(cells)]
        n = int(cell["L"] * n_frac)
        m = min(2 * n, cell["E"] // 4)
        indptr, indices, _ = launcher_gnm(n, m, seed + topo, lo, hi)
        for _ in range(min(repeat, n_requests - len(reqs))):
            w = rng.integers(lo, hi + 1, size=n).astype(np.int32)
            reqs.append((indptr, indices, w))
        topo += 1
    return reqs
