"""Small-size runs of the benchmark's cells on the CPU."""

import json
import os
import subprocess
import sys

from bench import run as R

ROOT = R.ROOT

#: traffic overrides that make each cell small enough for the CPU
SMALL = {
    "gnm18.reduce": {"n_per_pe": 512},
    "gnm11.rnp": {"n_per_pe": 256},
    "serve.mix16": {"batches": 1},
    "gnm14x4.reduce": {"n_per_pe": 128},
}

#: a cell whose driver, traffic and metric reader are kept for a later
#: benchmark change to name in BENCHMARK.json: DisReduA with one PE per
#: chip on four chips
LATER_CELL = dict(name="gnm14x4.reduce", config="gnm-d8",
                  traffic="reduce14x4", chips=4, why="x")
LATER_METRIC = dict(name="exchange_exposed_ms.reduce", unit="ms",
                    better="lower", source="device_trace", layer="exchange",
                    moves="reduce_s", workloads=["gnm14x4.reduce"])


def load_spec() -> dict:
    """BENCHMARK.json with the later cell named in it."""
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec["workloads"].append(LATER_CELL)
    reduce_s = next(m for m in spec["end_to_end"] if m["name"] == "reduce_s")
    reduce_s["workloads"].append(LATER_CELL["name"])
    spec["per_layer"].append(LATER_METRIC)
    return spec


def small_cell(workload, **override):
    spec = load_spec()
    cell, config, traffic = R.cell_spec(spec, workload)
    traffic = dict(traffic, **SMALL[workload], **override)
    return spec, cell, config, traffic


def measure_small(workload, seed=3, trace=False, **override):
    """One run of the cell at its small size, the look for a chip skipped."""
    spec, cell, config, traffic = small_cell(workload, **override)
    return R.measure(spec, cell, config, traffic, seed=seed, seconds=0.01,
                     trace=trace, require_chip=False)


def in_four_devices(code: str, timeout: int = 600) -> dict:
    """Run ``code`` (which prints one JSON line last) in a child process
    that sees four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
