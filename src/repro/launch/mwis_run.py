"""MWIS solver driver — the paper's workload end to end.

    PYTHONPATH=src python -m repro.launch.mwis_run \
        --family rhg --n 20000 --p 8 --algo rnp --mode async

Generates an instance, partitions it over ``--p`` PEs with halos, runs the
chosen distributed solver on the union simulation path (all PEs stacked on
one device), verifies independence and reports quality vs the sequential
baseline.  The one-PE-per-chip shard_map path is
``solvers.solver_shard_map_fn``; ``chip_smoke.py --chips 4`` drives it.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="rhg",
                    choices=("rhg", "rgg", "gnm"))
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--p", type=int, default=8)
    ap.add_argument("--algo", default="rnp",
                    choices=("reduce", "greedy", "rg", "rnp"))
    ap.add_argument("--mode", default="async", choices=("sync", "async"))
    ap.add_argument("--exchange", default="allgather",
                    choices=("allgather", "a2a"))
    ap.add_argument("--window-cap", type=int, default=16)
    ap.add_argument("--heavy-k", type=int, default=8)
    ap.add_argument("--schedule", default="cheap",
                    help="named rule schedule (repro.core.engine.SCHEDULES)")
    ap.add_argument("--backend", default="jnp",
                    choices=("jnp", "blocked", "pallas"),
                    help="aggregate backend for the rule-test reductions")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compare-seq", action="store_true")
    ap.add_argument("--bfs-relabel", action="store_true",
                    help="locality relabel (partitioning variant, Table C.3)")
    args = ap.parse_args()

    from repro.core import distributed as D, partition as part, solvers as S
    from repro.graphs import generators as gen
    from repro.graphs.relabel import relabel_bfs

    g = gen.FAMILIES[args.family](args.n, seed=args.seed)
    if args.bfs_relabel:
        g = relabel_bfs(g)
    print(f"instance: {args.family} n={g.n} m={g.m}")
    t0 = time.time()
    pg = part.partition_graph(g, args.p, window_cap=args.window_cap)
    print(f"partition: p={args.p} L={pg.L} G={pg.G} E={pg.E} "
          f"B={pg.B} ({time.time() - t0:.2f}s)")
    cfg = D.DisReduConfig(
        heavy_k=args.heavy_k, mode=args.mode, exchange=args.exchange,
        schedule=args.schedule, backend=args.backend,
    )

    if args.algo == "reduce":
        t0 = time.time()
        state, prob, rounds = D.disredu(pg, cfg)
        dt = time.time() - t0
        nv, ne = D.kernel_stats(pg, state)
        print(f"DisRedu{'A' if args.mode == 'async' else 'S'}: "
              f"rounds={rounds} time={dt:.2f}s "
              f"|V'|/|V|={nv / g.n:.4f} |E'|/|E|={ne / max(g.m, 1):.4f} "
              f"offset={int(state.offset)}")
        return

    t0 = time.time()
    members, state, iters = S.solve(pg, args.algo, cfg)
    dt = time.time() - t0
    assert g.is_independent_set(members), "solution must be independent!"
    w = g.set_weight(members)
    print(f"{args.algo}/{args.mode}: weight={w} |I|={members.sum()} "
          f"iterations={iters} time={dt:.2f}s")

    if args.compare_seq:
        from repro.core import sequential as seq

        t0 = time.time()
        w_seq, _ = seq.solve_reduce_and_peel(g)
        print(f"sequential RnP baseline: weight={w_seq} "
              f"time={time.time() - t0:.2f}s quality={w / max(w_seq, 1):.4f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
