"""Small-size runs of the benchmark's cells on the CPU."""

import json
import os
import subprocess
import sys

from bench import run as R

ROOT = R.ROOT


def load_spec(root: str = ROOT) -> dict:
    return R.load_json(os.path.join(root, "BENCHMARK.json"))


#: where ``<workload>.json`` holds the traffic overrides that make the cell
#: small enough for the CPU
SMALL_DIR = os.path.join("bench", "tests", "small")


def small_path(workload: str, root: str = ROOT) -> str:
    return os.path.join(root, SMALL_DIR, workload + ".json")


def small_cell(workload, root=ROOT, **override):
    spec = load_spec(root)
    cell, config, traffic = R.cell_spec(spec, workload, root)
    traffic = dict(traffic, **R.load_json(small_path(workload, root)),
                   **override)
    return spec, cell, config, traffic


def measure_small(workload, seed=3, trace=False, root=ROOT, **override):
    """One run of the cell at its small size, the look for a chip skipped."""
    spec, cell, config, traffic = small_cell(workload, root, **override)
    return R.measure(spec, cell, config, traffic, seed=seed, seconds=0.01,
                     trace=trace, require_chip=False, root=root)


def start_child(code: str, devices: int = 1) -> subprocess.Popen:
    """Start ``code`` (which prints one JSON line last) in a child process
    that sees ``devices`` CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    return subprocess.Popen([sys.executable, "-c", code], env=env, cwd=ROOT,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def child_result(child: subprocess.Popen, timeout: int = 600) -> dict:
    try:
        out, err = child.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        raise
    assert child.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


def in_four_devices(code: str, timeout: int = 600) -> dict:
    """Run ``code`` in a child process that sees four CPU devices."""
    return child_result(start_child(code, devices=4), timeout)
