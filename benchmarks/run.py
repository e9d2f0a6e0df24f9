# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--engine-only`` (or the default full run) also times one reduction
# sweep per aggregate backend and writes BENCH_engine.json.
# ``--serve`` runs the batched-serving throughput bench (BENCH_serve.json);
# see benchmarks/compare.py for the CI bench-regression gate.  On the CPU
# the pallas rows need REPRO_PALLAS_INTERPRET=1 (interpret mode on request).
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _engine_bench(out_path: str, small: bool = False) -> None:
    from benchmarks.engine_bench import run_engine_bench

    try:
        from tests import seed_oracle
    except ImportError:
        seed_oracle = None
    payload = run_engine_bench(out_path, seed_oracle=seed_oracle,
                               small=small)
    for row in payload["results"]:
        for backend, us in row["per_sweep_us"].items():
            print(f"engine_sweep/{row['graph']}/{backend},{us:.1f},"
                  f"schedule={row['schedule']}", flush=True)
        for kind in ("greedy_round_us", "rnp_round_us"):
            for backend, us in row.get(kind, {}).items():
                print(f"engine_{kind[:-3]}/{row['graph']}/{backend},"
                      f"{us:.1f},schedule={row['schedule']}", flush=True)
    print(f"# wrote {out_path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine-only", action="store_true",
                    help="only the aggregate-engine sweep bench + "
                         "BENCH_engine.json")
    ap.add_argument("--engine-small", action="store_true",
                    help="CI-sized engine bench: one small cell, jnp + "
                         "blocked + pallas-interpret, few reps")
    ap.add_argument("--skip-engine", action="store_true",
                    help="paper tables only, no BENCH_engine.json")
    ap.add_argument("--engine-out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_engine.json"))
    ap.add_argument("--serve", action="store_true",
                    help="batched-serving throughput bench -> "
                         "BENCH_serve.json (with --engine-small: CI-sized)")
    ap.add_argument("--serve-out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.serve:
        from benchmarks.serve_bench import run_serve_bench

        run_serve_bench(args.serve_out, small=args.engine_small)
        print(f"# wrote {args.serve_out}", flush=True)
        return

    print("name,us_per_call,derived")
    if not args.engine_only:
        from benchmarks import paper_tables

        for bench in paper_tables.ALL:
            for name, us, derived in bench():
                print(f"{name},{us:.1f},{derived}", flush=True)
    if not args.skip_engine:
        _engine_bench(args.engine_out, small=args.engine_small)


if __name__ == "__main__":
    main()
