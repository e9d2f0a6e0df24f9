"""Serving driver: batched MWIS solving, DLRM scoring, or LM decode.

The default ``mwis`` arch drives the batched many-instance front end
(:mod:`repro.core.serve`): a stream of random instances is bucketed into
the static serve cells, topology-cached, and solved as vmapped batches;
the driver reports sustained instances/sec, p50/p99 batch latency, and
plan-cache statistics.  It exits non-zero when any request errored or
failed verification, or when the configured backend was demoted down the
fallback chain — a demoted run is not a run of the backend asked for.

    PYTHONPATH=src python -m repro.launch.serve --arch mwis --requests 64
    PYTHONPATH=src python -m repro.launch.serve --arch mwis --algo rnp \\
        --backend blocked --batch 16 --repeat-topologies 4
    PYTHONPATH=src python -m repro.launch.serve --arch dlrm-mlperf --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --tokens 16
"""

from __future__ import annotations

import argparse
import sys
import time

ARCHES = ("mwis", "dlrm-mlperf", "gemma3-1b", "qwen3-32b",
          "mistral-nemo-12b")


def mwis_requests(cells, n_requests: int, repeat_topologies: int,
                  seed: int) -> list:
    """The launcher's instance stream: cycle the serve cells with one GNM
    topology each (80% of the cell's vertices), each repeated with fresh
    weights — the production re-auction pattern."""
    import numpy as np

    from repro.graphs.generators import gnm

    rng = np.random.default_rng(seed)
    reqs = []
    topo = 0
    while len(reqs) < n_requests:
        cell = cells[topo % len(cells)]
        n = int(cell.L * 0.8)
        m = min(2 * n, cell.E // 4)
        g = gnm(n, m, seed=seed + topo)
        for _ in range(repeat_topologies):
            w = rng.integers(1, 201, size=g.n).astype(np.int32)
            reqs.append(type(g)(indptr=g.indptr, indices=g.indices,
                                weights=w))
            if len(reqs) == n_requests:
                break
        topo += 1
    return reqs


def _serve_mwis(args) -> None:
    import jax

    from repro.core import serve as SV

    cfg = SV.ServeConfig(algo=args.algo, backend=args.backend,
                         max_batch=args.batch, verify=args.verify,
                         descent=args.descent, devices=args.devices,
                         pipeline=not args.no_pipeline)
    try:
        svc = SV.MWISService(cfg)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)
    cells = svc.cells
    ndev = svc.stats["devices"]
    print(f"mwis service: algo={cfg.algo} backend={cfg.backend} "
          f"verify={cfg.verify} descent={cfg.descent} "
          f"batch<={cfg.max_batch} cells="
          f"{[f'{c.name}(L={c.L},E={c.E})' for c in cells]}")
    print(f"devices: {ndev}/{jax.device_count()} visible "
          f"({jax.default_backend()}) "
          f"pipeline={'on' if cfg.pipeline else 'off'}")

    reqs = mwis_requests(cells, args.requests, args.repeat_topologies,
                         args.seed)
    batches = [reqs[i:i + args.batch]
               for i in range(0, len(reqs), args.batch)]
    stats = SV.measure_throughput(svc, batches, warmup=1)
    tot_w = 0
    n_err = 0
    for b in batches:
        rs = svc.solve_batch(list(b))
        tot_w += sum(r.weight for r in rs)
        n_err += sum(not r.ok for r in rs)
    print(f"requests={stats['instances']} batches={stats['batches']} "
          f"throughput={stats['instances_per_sec']:.1f} inst/s")
    print(f"p50={stats['p50_ms']:.2f}ms p99={stats['p99_ms']:.2f}ms "
          f"(per-batch latency)")
    print(f"total solution weight (last pass): {tot_w} "
          f"({n_err} per-request errors)")
    s = svc.stats
    print(f"cache: hits={s['cache_hits']} misses={s['cache_misses']} "
          f"evictions={s['cache_evictions']} errors={s['cache_errors']} "
          f"size={s['cache_size']} programs={s['programs']} "
          f"compiles={s['compiles']}")
    print(f"robustness: backend={s['backend']}"
          f"{'' if s['backend_active'] == s['backend'] else ' -> ' + s['backend_active']} "
          f"rejected={s['rejected']} repaired={s['repaired']} "
          f"pack_errors={s['pack_errors']} solve_errors={s['solve_errors']} "
          f"fallbacks={s['fallbacks']} "
          f"verified={s['verify_checked']}/{s['verify_failures']} "
          f"(checked/failed)")
    print(f"descent: mode={cfg.descent} "
          f"solves={s['descent_solves']} descents={s['descents']} "
          f"oversize_admitted={s['oversize_admitted']} "
          f"plan_cache_hits={s['cache_descent_hits']}/"
          f"{s['cache_descent_hits'] + s['cache_descent_misses']}")
    p50 = s["stage_p50_ms"]
    print(f"stages (p50/chunk): pack={p50['pack']:.2f}ms "
          f"transfer={p50['transfer']:.2f}ms solve={p50['solve']:.2f}ms "
          f"fetch={p50['fetch']:.2f}ms")
    print(f"pipeline: devices={s['devices']} chunks={s['chunks']} "
          f"pipelined={s['pipelined_chunks']} "
          f"retries={s['pipeline_retries']} "
          f"overlap_ratio={s['overlap_ratio']:.3f}")
    faults = {k: s[k] for k in ("pack_errors", "solve_errors", "fallbacks",
                                "verify_failures") if s[k]}
    if n_err or faults or s["backend_active"] != s["backend"]:
        print(f"error: {n_err} failed request(s) in the last pass, "
              f"backend {s['backend']} -> {s['backend_active']}, {faults}",
              file=sys.stderr)
        raise SystemExit(1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="mwis", choices=ARCHES)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    # mwis-only knobs
    ap.add_argument("--algo", default="rg",
                    choices=("greedy", "rg", "rnp"))
    ap.add_argument("--backend", default="jnp",
                    choices=("jnp", "blocked", "pallas"))
    ap.add_argument("--repeat-topologies", type=int, default=4,
                    help="requests sharing one topology (fresh weights)")
    ap.add_argument("--verify", default="off",
                    choices=("off", "sample", "full"),
                    help="post-solve output audit (independence + weight)")
    ap.add_argument("--descent", default="off", choices=("off", "auto"),
                    help="shape descent: big cells shrink mid-solve and "
                         "oversize instances enter via descent cells")
    ap.add_argument("--devices", type=int, default=None,
                    help="serve-mesh size for the sharded batch axis "
                         "(default: every visible device; exits with an "
                         "error when more are requested than exist)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable the overlapped host pack/transfer "
                         "pipeline (chunks run synchronously)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.arch == "mwis":
        _serve_mwis(args)
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import common as MC

    if args.arch == "dlrm-mlperf":
        from repro.configs.dlrm_mlperf import SMOKE as cfg
        from repro.data.pipeline import DLRMBatchSpec, dlrm_batch
        from repro.models import dlrm as M

        params = MC.init_params(M.param_specs(cfg), jax.random.key(0))
        serve = jax.jit(lambda p, b: M.serve_step(p, b, cfg))
        spec = DLRMBatchSpec(args.batch, cfg.n_dense, cfg.n_sparse,
                             cfg.vocabs)
        lat = []
        for r in range(args.requests):
            b = dlrm_batch(spec, r)
            b.pop("labels")
            t0 = time.perf_counter()
            probs = serve(params, {k: jnp.asarray(v) for k, v in b.items()})
            probs.block_until_ready()
            lat.append((time.perf_counter() - t0) * 1e3)
            print(f"request {r}: batch={args.batch} "
                  f"mean_ctr={float(probs.mean()):.4f} "
                  f"lat={lat[-1]:.2f}ms")
        lat = np.asarray(lat[1:])  # drop compile
        print(f"p50={np.percentile(lat, 50):.2f}ms "
              f"p99={np.percentile(lat, 99):.2f}ms")
        return

    # LM decode
    from repro.configs import gemma3_1b, mistral_nemo_12b, qwen3_32b

    smokes = {
        "gemma3-1b": gemma3_1b.SMOKE,
        "qwen3-32b": qwen3_32b.SMOKE,
        "mistral-nemo-12b": mistral_nemo_12b.SMOKE,
    }
    cfg = smokes[args.arch]
    from repro.models import transformer as T

    params = MC.init_params(T.param_specs(cfg), jax.random.key(0))
    B, S = args.batch, args.tokens + 8
    (kc_abs, vc_abs), _ = T.make_kv_cache_specs(cfg, B, S)
    kc = jnp.zeros(kc_abs.shape, kc_abs.dtype)
    vc = jnp.zeros(vc_abs.shape, vc_abs.dtype)

    decode = jax.jit(
        lambda p, kc, vc, tok, pos: T.serve_step(p, (kc, vc), tok, pos, cfg)
    )
    tok = jnp.zeros((B, 1), jnp.int32)
    t0 = time.perf_counter()
    for t in range(args.tokens):
        logits, (kc, vc) = decode(params, kc, vc, tok,
                                  jnp.asarray(t, jnp.int32))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens x batch {B} in {dt:.2f}s "
          f"({args.tokens * B / dt:.1f} tok/s, incl. compile)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
