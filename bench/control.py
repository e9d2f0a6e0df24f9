"""The control of a cell's comparison: the reference in bfloat16, put in
the program's place, must come out as not correct.

    python3 bench/control.py --workload gnm18.reduce --seeds 5 6 7

For each seed the cell's inputs are made as a run makes them; the answers a
run would take from the system come instead from the reference with every
neighbourhood weight sum and the folded-weight offset kept in bfloat16
(``reference.Reducer(lowp=True)``); the run's own comparison then reads
them against the exact reference.  One JSON line per seed: the numbers
compared, each with its limit, and ``correct``.  The benchmark's runs never
run it; it needs no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control(workload: str, seed: int, root: str = ROOT, traffic=None,
            spec=None) -> dict:
    """The control's readings for one seed (``traffic`` overrides the
    cell's traffic file, for tests at a small size; ``spec`` the contents
    of ``BENCHMARK.json``)."""
    from bench import cells as C
    from bench.run import cell_spec, load_json

    spec = spec or load_json(os.path.join(root, "BENCHMARK.json"))
    _, config, cell_traffic = cell_spec(spec, workload, root)
    traffic = traffic or cell_traffic
    cell = C.kind(traffic["kind"], root)(config, traffic, seed, C.Spans(),
                                         root=root)
    cell.make_inputs()
    cell.control_answers()
    gaps, failed = cell.check()
    checks = {k: dict(value=v, limit=config["limits"][k])
              for k, v in gaps.items()}
    return dict(workload=workload, seed=seed, failed=failed,
                correct=all(c["value"] <= c["limit"]
                            for c in checks.values()),
                checks=checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    for seed in args.seeds:
        print(json.dumps(control(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
