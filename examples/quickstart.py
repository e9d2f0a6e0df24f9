"""Quickstart: distributed MWIS reduction + reduce-and-peel in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import distributed as D, partition as part, solvers as S
from repro.graphs import generators as gen

# 1. an instance: random hyperbolic-ish graph, uniform weights in [1, 200]
g = gen.rhg_like(5000, avg_deg=8, seed=0)
print(f"graph: n={g.n} m={g.m}")

# 2. partition over p=8 PEs with ghost halos (the paper's machine model)
pg = part.partition_graph(g, p=8, window_cap=16)

# 3. DisReduA: asynchronous distributed reductions to the global fixpoint
state, prob, rounds = D.disredu(pg, D.DisReduConfig(mode="async"))
nv, ne = D.kernel_stats(pg, state)
print(f"DisReduA: {rounds} rounds, kernel |V'|/|V|={nv / g.n:.4f} "
      f"|E'|/|E|={ne / max(g.m, 1):.4f}")

# 4. full reduce-and-peel solver (RnPA) + verification
members, _, _ = S.solve(pg, "rnp", D.DisReduConfig(mode="async"))
assert g.is_independent_set(members)
print(f"RnPA solution: weight={g.set_weight(members)} size={members.sum()}")
