"""Sustained-throughput benchmark for the batched MWIS serving layer.

Measures instances/sec and p50/p99 per-batch latency for each
(serve cell × backend × batch size) program of :mod:`repro.core.serve`,
in the steady serving state (all programs compiled, all topologies
cached, fresh weights per request).  Writes ``BENCH_serve.json``.

Full mode covers every serve cell at two batch sizes on the jnp backend
plus blocked and pallas-interpret on the smallest cell (the interpret
rows are CPU-simulation numbers, not TPU projections).  ``small=True``
is the CI shape: smallest cell only, jnp + blocked, few requests.

Every row carries the per-stage breakdown (pack / H2D transfer / solve /
fetch ms and the pipeline overlap ratio) from ``MWISService.stats``.
Batch-4 rows get an ``instances_per_sec_pipelined`` column driven with
multi-chunk calls (4 chunks per ``solve_batch``) so the overlapped host
pipeline actually engages.  A ``devices=N`` multi-device section shards
the batch axis over a ``serve`` mesh, in this process when N devices are
visible.  On the CPU with fewer, the rows run in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (CPU emulation:
correctness + overlap surface, not real accelerator speedup).  On an
accelerator with fewer the section fails: a child would find the chip held
by this process.

On the CPU the pallas rows run the kernel in interpret mode, which must be
asked for with ``REPRO_PALLAS_INTERPRET=1``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MULTIDEVICE_N = 4


def _stage_cols(svc) -> dict:
    """Per-stage timing columns of a driven service (cumulative)."""
    s = svc.stats
    return dict(
        devices=s["devices"],
        stage_ms=s["stage_ms"],
        stage_p50_ms=s["stage_p50_ms"],
        overlap_ratio=s["overlap_ratio"],
        chunks=s["chunks"],
        pipelined_chunks=s["pipelined_chunks"],
    )


def _instance_stream(cell, n_topologies: int, repeats: int, seed: int):
    """Request list for one cell: n_topologies graphs sized to ~80% of the
    cell, each repeated with fresh weights (the re-auction pattern)."""
    import numpy as np

    from repro.graphs.generators import gnm

    rng = np.random.default_rng(seed)
    reqs = []
    for t in range(n_topologies):
        n = max(8, int(cell.L * 0.8))
        m = min(2 * n, cell.E // 4)
        g = gnm(n, m, seed=seed + t)
        for _ in range(repeats):
            w = rng.integers(1, 201, size=g.n).astype(np.int32)
            reqs.append(type(g)(indptr=g.indptr, indices=g.indices,
                                weights=w))
    return reqs


def _multidevice_rows(small: bool, devices: int) -> list:
    """Benchmark rows with the batch axis sharded over ``devices``.

    Must run in a process where ``jax.device_count() >= devices`` —
    either real accelerators or CPU host devices forced via XLA_FLAGS.
    Calls carry 4 chunks of ``batch`` requests so pipelining engages.
    """
    from repro.core import serve as SV

    cells = SV.serve_cells()
    if small:
        plan = [(cells[0], 4, "jnp")]
        n_chunks = 2
    else:
        plan = [(c, 4, "jnp") for c in cells]
        plan += [(cells[min(1, len(cells) - 1)], 16, "jnp")]
        n_chunks = 4
    rows = []
    for cell, batch, backend in plan:
        svc = SV.MWISService(
            SV.ServeConfig(algo="rg", backend=backend, max_batch=batch,
                           devices=devices)
        )
        reqs = _instance_stream(cell, n_chunks, batch, seed=17)
        stats = SV.measure_throughput(svc, [reqs], warmup=1)
        rows.append(dict(
            cell=cell.name, backend=backend, batch=batch,
            L=cell.L, E=cell.E,
            instances_per_sec=stats["instances_per_sec"],
            p50_ms=stats["p50_ms"], p99_ms=stats["p99_ms"],
            instances=stats["instances"],
            **_stage_cols(svc),
        ))
    return rows


def _multidevice_section(small: bool, devices: int = MULTIDEVICE_N) -> list:
    """Multi-device rows: in this process when enough devices are
    visible; on the CPU otherwise in a child with forced host devices.
    Raises when the section cannot run."""
    import jax

    if jax.device_count() >= devices:
        return _multidevice_rows(small, devices)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"multidevice section needs {devices} devices, "
            f"{jax.device_count()} {jax.default_backend()} device(s) visible")
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       ".serve_md_rows.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    cmd = [sys.executable, os.path.abspath(__file__),
           "--multidevice-child", out, str(devices)]
    if small:
        cmd.append("--small")
    subprocess.run(cmd, env=env, check=True, timeout=3600)
    with open(out) as f:
        rows = json.load(f)
    os.remove(out)
    return rows


def run_serve_bench(out_path: str, small: bool = False) -> dict:
    import jax

    from repro.core import serve as SV

    cells = SV.serve_cells()
    if small:
        plan = [(cells[0], b, bk)
                for b in (1, 4) for bk in ("jnp", "blocked")]
        n_topologies, repeats = 2, 2
    else:
        plan = [(c, b, "jnp") for c in cells for b in (4, 16)]
        plan += [(cells[0], 4, "blocked"), (cells[0], 4, "pallas")]
        n_topologies, repeats = 4, 4

    results = []
    for cell, batch, backend in plan:
        svc = SV.MWISService(
            SV.ServeConfig(algo="rg", backend=backend, max_batch=batch)
        )
        reqs = _instance_stream(cell, n_topologies, repeats, seed=17)
        batches = [reqs[i:i + batch] for i in range(0, len(reqs), batch)]
        stats = SV.measure_throughput(svc, batches, warmup=1)
        # second pass with verify=full on the same topology cache: the
        # delta is the pure post-solve audit cost (independence check +
        # weight recomputation per request)
        svc_v = SV.MWISService(
            SV.ServeConfig(algo="rg", backend=backend, max_batch=batch,
                           verify="full")
        )
        stats_v = SV.measure_throughput(svc_v, batches, warmup=1)
        ips, ips_v = stats["instances_per_sec"], stats_v["instances_per_sec"]
        overhead = round(100.0 * (ips - ips_v) / ips, 1) if ips else 0.0
        label = "pallas-interpret" if backend == "pallas" else backend
        row = dict(
            cell=cell.name, backend=label, batch=batch,
            L=cell.L, E=cell.E,
            instances_per_sec=ips,
            instances_per_sec_verify_full=ips_v,
            verify_full_overhead_pct=overhead,
            p50_ms=stats["p50_ms"], p99_ms=stats["p99_ms"],
            instances=stats["instances"],
            cache=svc.stats,
            **_stage_cols(svc),
        )
        if batch >= 4 and backend == "jnp":
            # multi-chunk calls (4 x batch requests, max_batch=batch) so
            # chunk k+1's host pack/H2D hides under chunk k's solve
            svc_p = SV.MWISService(
                SV.ServeConfig(algo="rg", backend=backend, max_batch=batch)
            )
            reqs_p = _instance_stream(cell, 4, batch, seed=17)
            stats_p = SV.measure_throughput(svc_p, [reqs_p], warmup=1)
            row["instances_per_sec_pipelined"] = \
                stats_p["instances_per_sec"]
            row["overlap_ratio_pipelined"] = \
                svc_p.stats["overlap_ratio"]
        results.append(row)
        print(f"serve/{cell.name}/{label}/b{batch},"
              f"{ips},"
              f"p50={stats['p50_ms']}ms p99={stats['p99_ms']}ms "
              f"verify_full={ips_v} ({overhead}% overhead)"
              + (f" pipelined={row['instances_per_sec_pipelined']}"
                 f" overlap={row['overlap_ratio_pipelined']}"
                 if "instances_per_sec_pipelined" in row else ""),
              flush=True)

    # ---- shape-descent rows: biggest cell, fixed vs descent="auto" ---- #
    # (the staged path solves per-instance, so this also measures the
    # descent overhead against the batched fixed-shape program)
    descent_rows = []
    d_cell = cells[-1]
    d_plan = [("jnp", 1)] if small else [("jnp", 1), ("blocked", 1)]
    nt, rp = (1, 2) if small else (2, 3)
    for backend, batch in d_plan:
        reqs = _instance_stream(d_cell, nt, rp, seed=23)
        batches = [reqs[i:i + batch] for i in range(0, len(reqs), batch)]
        svc_off = SV.MWISService(
            SV.ServeConfig(algo="rg", backend=backend, max_batch=batch))
        svc_on = SV.MWISService(
            SV.ServeConfig(algo="rg", backend=backend, max_batch=batch,
                           descent="auto", descent_min_L=d_cell.L))
        stats_off = SV.measure_throughput(svc_off, batches, warmup=1)
        stats_on = SV.measure_throughput(svc_on, batches, warmup=1)
        s = svc_on.stats
        row = dict(
            cell=d_cell.name, backend=backend, batch=batch,
            instances_per_sec_fixed=stats_off["instances_per_sec"],
            instances_per_sec_descent=stats_on["instances_per_sec"],
            p50_ms_fixed=stats_off["p50_ms"],
            p50_ms_descent=stats_on["p50_ms"],
            descent_solves=s["descent_solves"], descents=s["descents"],
            oversize_admitted=s["oversize_admitted"],
            cache_descent_hits=s["cache_descent_hits"],
            cache_descent_misses=s["cache_descent_misses"],
        )
        descent_rows.append(row)
        print(f"serve-descent/{d_cell.name}/{backend}/b{batch},"
              f"fixed={row['instances_per_sec_fixed']} "
              f"descent={row['instances_per_sec_descent']} inst/s "
              f"(descents={row['descents']})", flush=True)

    # ---- multi-device rows: batch axis sharded over a serve mesh ------ #
    md_rows = _multidevice_section(small)
    for row in md_rows:
        print(f"serve-md/{row['cell']}/{row['backend']}"
              f"/b{row['batch']}/d{row['devices']},"
              f"{row['instances_per_sec']},"
              f"overlap={row['overlap_ratio']} "
              f"stage_p50={row['stage_p50_ms']}", flush=True)

    payload = dict(
        meta=dict(
            unit="sustained instances/sec + per-batch latency ms, steady "
                 "state (programs compiled, topologies cached, fresh "
                 "weights per request)",
            jax=jax.__version__,
            device=jax.default_backend(),
            small=small,
            note="pallas-interpret rows run the kernel in CPU interpret "
                 "mode — correctness surface, not TPU performance",
            verify_note="instances_per_sec_verify_full re-runs the same "
                        "stream with ServeConfig.verify='full' (post-solve "
                        "independence + weight audit on every request)",
            descent_note="descent rows compare the batched fixed-shape "
                         "program against the per-instance shape-descent "
                         "path (ServeConfig.descent='auto') on the "
                         "biggest serve cell",
            multidevice_note=f"multidevice rows shard the batch axis over "
                             f"a {MULTIDEVICE_N}-device serve mesh, driven "
                             f"with multi-chunk calls so the host pipeline "
                             f"engages; on CPU they run in a subprocess "
                             f"with forced host devices (correctness + "
                             f"overlap surface, not accelerator speedup)",
        ),
        results=results,
        descent=descent_rows,
        multidevice=md_rows,
    )
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    return payload


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    small = "--small" in sys.argv
    if "--multidevice-child" in sys.argv:
        # child mode: XLA_FLAGS is already in the environment (set by the
        # parent BEFORE this process imports jax) — write rows and exit
        i = sys.argv.index("--multidevice-child")
        child_out, devices = sys.argv[i + 1], int(sys.argv[i + 2])
        rows = _multidevice_rows(small, devices)
        with open(child_out, "w") as f:
            json.dump(rows, f)
        sys.exit(0)
    out = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
    run_serve_bench(out, small=small)
    print(f"# wrote {out}")
