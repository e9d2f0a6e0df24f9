"""End-to-end driver: the paper's full pipeline on all three weak-scaling
graph families, with quality/impact comparison against the sequential
HtWIS-style baseline (Table 7.1 / 7.2 / C.4 at laptop scale).

    PYTHONPATH=src python examples/reduce_and_peel.py [--n 4000] [--p 8]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--p", type=int, default=8)
    args = ap.parse_args()

    from repro.core import distributed as D, partition as part, solvers as S
    from repro.core import sequential as seq
    from repro.graphs import generators as gen

    print(f"{'family':6s} {'algo':6s} {'weight':>10s} {'quality':>8s} "
          f"{'V`/V':>7s} {'time':>7s}")
    for fam in ("gnm", "rgg", "rhg"):
        g = gen.FAMILIES[fam](args.n, seed=0)
        t0 = time.time()
        w_seq, _ = seq.solve_reduce_and_peel(g)
        t_seq = time.time() - t0
        print(f"{fam:6s} {'seq':6s} {w_seq:10d} {'1.000':>8s} "
              f"{'-':>7s} {t_seq:6.2f}s")
        pg = part.partition_graph(g, args.p, window_cap=16)
        state, prob, _ = D.disredu(pg, D.DisReduConfig(mode='async'))
        nv, _ = D.kernel_stats(pg, state)
        for algo in ("greedy", "rg", "rnp"):
            pg2 = part.partition_graph(g, args.p, window_cap=16)
            t0 = time.time()
            members, _, _ = S.solve(pg2, algo, D.DisReduConfig(mode="async"))
            dt = time.time() - t0
            assert g.is_independent_set(members)
            w = g.set_weight(members)
            print(f"{fam:6s} {algo:6s} {w:10d} {w / max(w_seq, 1):8.4f} "
                  f"{nv / g.n:7.4f} {dt:6.2f}s")


if __name__ == "__main__":
    main()
