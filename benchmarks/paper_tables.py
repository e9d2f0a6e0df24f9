"""Benchmarks — one per paper table/figure, at laptop scale.

| bench                      | paper artifact                  |
|----------------------------|---------------------------------|
| reduction_impact           | Fig 7.1 / Table C.2             |
| reduction_partitioned      | Table C.3 (partitioning variant)|
| solver_quality             | Table 7.1                       |
| weak_scaling               | Table 7.2 / C.4 / Fig 7.3       |
| kernel_micro               | (framework) Pallas-kernel refs  |

Each function yields CSV rows: name,us_per_call,derived
(derived = the table's own metric: |V'|/|V|, ω/ω_best, edges/s, ...).
"""

from __future__ import annotations

import time
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]


def _timed(fn, *args, reps: int = 1):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out) if hasattr(out, "block_until_ready") else None
    return out, (time.perf_counter() - t0) / reps * 1e6


def bench_reduction_impact() -> Iterator[Row]:
    """Fig 7.1 / Table C.2: kernel size + reduce time vs p, sync vs async."""
    from repro.core import distributed as D, partition as part
    from repro.graphs import generators as gen

    g = gen.rgg2d(4000, avg_deg=8, seed=0)
    for mode in ("sync", "async"):
        for p in (1, 4, 8):
            pg = part.partition_graph(g, p, window_cap=12)
            cfg = D.DisReduConfig(heavy_k=8, mode=mode)

            def run():
                state, prob, rounds = D.disredu(pg, cfg)
                return state

            t0 = time.perf_counter()
            state = run()  # includes compile on first variant
            t0 = time.perf_counter()
            state = run()
            us = (time.perf_counter() - t0) * 1e6
            nv, ne = D.kernel_stats(pg, state)
            name = "DisRedu" + ("S" if mode == "sync" else "A")
            yield (
                f"reduction_impact/{name}/p{p}", us,
                f"V'/V={nv / g.n:.4f};E'/E={ne / g.m:.4f}",
            )


def bench_reduction_partitioned() -> Iterator[Row]:
    """Table C.3: locality-aware order (partitioning stand-in) vs natural."""
    from repro.core import distributed as D, partition as part
    from repro.graphs import generators as gen
    from repro.graphs.relabel import cut_edges_fraction, relabel_bfs

    g = gen.rgg2d(4000, avg_deg=8, seed=1)
    for label, graph in (("natural", g), ("bfs", relabel_bfs(g))):
        pg = part.partition_graph(graph, 8, window_cap=12)
        t0 = time.perf_counter()
        state, prob, _ = D.disredu(pg, D.DisReduConfig(heavy_k=8))
        us = (time.perf_counter() - t0) * 1e6
        nv, _ = D.kernel_stats(pg, state)
        cut = cut_edges_fraction(graph, 8)
        yield (
            f"reduction_partitioned/{label}/p8", us,
            f"V'/V={nv / graph.n:.4f};cut={cut:.3f}",
        )


def bench_solver_quality() -> Iterator[Row]:
    """Table 7.1: quality vs best-found + runtime, all six solvers + seq."""
    from repro.core import distributed as D, partition as part, solvers as S
    from repro.core import sequential as seq
    from repro.graphs import generators as gen

    g = gen.rgg2d(3000, avg_deg=8, seed=2)
    results = {}
    t0 = time.perf_counter()
    w_htwis, _ = seq.solve_reduce_and_peel(g)
    t_htwis = (time.perf_counter() - t0) * 1e6
    results["HtWIS-seq"] = (w_htwis, t_htwis)
    for algo, tag in (("greedy", "G"), ("rg", "RG"), ("rnp", "RnP")):
        for mode, sfx in (("sync", "S"), ("async", "A")):
            pg = part.partition_graph(g, 4, window_cap=12)
            cfg = D.DisReduConfig(heavy_k=8, mode=mode)
            S.solve(pg, algo, cfg)  # compile
            t0 = time.perf_counter()
            members, _, _ = S.solve(pg, algo, cfg)
            us = (time.perf_counter() - t0) * 1e6
            results[f"{tag}{sfx}"] = (g.set_weight(members), us)
    best = max(w for w, _ in results.values())
    for name, (w, us) in results.items():
        yield (
            f"solver_quality/{name}/p4", us,
            f"quality={w / best:.4f}",
        )


def bench_weak_scaling() -> Iterator[Row]:
    """Table 7.2/C.4 + Fig 7.3: per-family kernel size, quality, throughput
    with fixed per-PE size (n/p const)."""
    from repro.core import distributed as D, partition as part, solvers as S
    from repro.graphs import generators as gen

    per_pe = 800
    for fam in ("gnm", "rgg", "rhg"):
        for p in (1, 4, 8):
            g = gen.FAMILIES[fam](per_pe * p, seed=3)
            pg = part.partition_graph(g, p, window_cap=12)
            cfg = D.DisReduConfig(heavy_k=8, mode="async")
            t0 = time.perf_counter()
            state, prob, _ = D.disredu(pg, cfg)
            dt = time.perf_counter() - t0
            nv, _ = D.kernel_stats(pg, state)
            members, _, _ = S.solve(pg, "rnp", cfg)
            q = g.set_weight(members)
            yield (
                f"weak_scaling/{fam}/p{p}", dt * 1e6,
                f"V'/V={nv / g.n:.4f};rnp_w={q};eps={g.m / max(dt, 1e-9):.0f}",
            )


def bench_kernel_micro() -> Iterator[Row]:
    """Framework kernels: jnp reference timings (CPU) + shapes."""
    from repro.kernels.embedding_bag.ref import embedding_bag_ref
    from repro.kernels.segment_coo.ops import pack_blocks, segment_sum_coo
    from repro.kernels.wedge_intersect.ref import wedge_intersect_ref

    rng = np.random.default_rng(0)
    # segment_coo
    n, e, d = 5000, 40000, 128
    row = rng.integers(0, n, size=e).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
    perm, lrow, _ = pack_blocks(row, n, r_blk=8)
    fn = jax.jit(lambda dt: segment_sum_coo(
        dt, jnp.asarray(perm), jnp.asarray(lrow), n, r_blk=8,
        force_pallas=False,
    ))
    _, us = _timed(fn, data, reps=5)
    yield ("kernel/segment_coo/e40k_d128", us, f"gbps={e * d * 8 / us / 1e3:.2f}")

    # wedge_intersect
    E, D = 20000, 16
    wu = jnp.asarray(rng.integers(0, 999, size=(E, D)), jnp.int32)
    awu = jnp.asarray(rng.integers(0, 200, size=(E, D)), jnp.int32)
    actu = jnp.asarray(rng.integers(0, 2, size=(E, D)), jnp.int32)
    fn = jax.jit(lambda a, b, c, dd: wedge_intersect_ref(a, b, c, dd))
    _, us = _timed(fn, wu, wu, awu, actu, reps=5)
    yield ("kernel/wedge_intersect/e20k_d16", us,
           f"medges_s={E / us:.2f}")

    # embedding_bag
    V, B, K, dim = 100_000, 8192, 4, 128
    table = jnp.asarray(rng.normal(size=(V, dim)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, V, size=(B, K)), jnp.int32)
    wgt = jnp.asarray(rng.normal(size=(B, K)), jnp.float32)
    fn = jax.jit(embedding_bag_ref)
    _, us = _timed(fn, table, idx, wgt, reps=5)
    yield ("kernel/embedding_bag/b8192_k4_d128", us,
           f"mlookups_s={B * K / us:.2f}")





def bench_kernel_compaction() -> Iterator[Row]:
    """Beyond-paper §Perf H3.4: adaptive shape descent between reduce
    stages (static-shape analogue of the paper's dependency checking)."""
    import time as _t

    from repro.core import distributed as D, partition as part, solvers as S
    from repro.graphs import generators as gen

    g = gen.rgg2d(6000, avg_deg=8, seed=3)
    cfg = D.DisReduConfig(mode="async", heavy_k=8)
    dcfg = D.DisReduConfig(mode="async", heavy_k=8, descent=True,
                           descent_every=2)
    S.solve(part.partition_graph(g, 8, window_cap=16), "rnp", cfg)  # warm
    t0 = _t.perf_counter()
    m1, _, _ = S.solve(part.partition_graph(g, 8, window_cap=16), "rnp", cfg)
    t_plain = _t.perf_counter() - t0
    S.solve_staged(g, 8, "rnp", dcfg)  # warm
    t0 = _t.perf_counter()
    m2, st = S.solve_staged(g, 8, "rnp", dcfg)
    t_comp = _t.perf_counter() - t0
    w1, w2 = g.set_weight(m1), g.set_weight(m2)
    yield ("compaction/plain_rnp/p8", t_plain * 1e6, f"w={w1}")
    yield (
        "compaction/descent_rnp/p8", t_comp * 1e6,
        f"w={w2};speedup={t_plain / max(t_comp, 1e-9):.2f}x;"
        f"descents={st['descents']};kernel={st['kernel_ratio']:.3f}",
    )


ALL = (
    bench_reduction_impact,
    bench_reduction_partitioned,
    bench_solver_quality,
    bench_weak_scaling,
    bench_kernel_micro,
    bench_kernel_compaction,
)
