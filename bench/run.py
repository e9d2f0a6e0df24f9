"""Run one benchmark cell on the chips of this machine; print one result.

    python3 bench/run.py --workload gnm18.reduce --seed 7 --seconds 10 --trace 0

The cell, its configuration and its traffic come from ``BENCHMARK.json`` at
the root of the checkout and the files it names: ``bench/configs/<config>
.json`` and ``bench/traffic/<traffic>.json``; the traffic's ``kind`` names
the cell class (``bench/cells.py`` ``kind``); a per-layer metric ``<name>`` is
read by ``bench/metrics/<name>.py``.  The run loads, warms up (that is
``setup_s``), measures for ``--seconds`` in whole passes over the cell's
inputs, reads the device's peak memory, frees the program's state and then
compares the answers with the plain reference (``bench/reference.py``).

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` they are its per-layer ones, and the window's first call is
traced (the traced window) into a directory of the run's own under
``.bench_trace/``, removed once the metrics are read.
The last line of stdout is the result; the numbers compared, each beside
its limit, are the last lines of stderr and the result's last key.  The run
exits 1, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when the system under test is not in the checkout.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(spec: dict, workload: str, root: str = ROOT):
    """(cell, configuration, traffic) named by ``workload``; the files are
    found by name under ``root``."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metric_sets(spec: dict, workload: str):
    """The cell's end-to-end metrics and the per-layer metrics it reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


def reader(name: str, root: str = ROOT):
    """The ``read`` function of the per-layer metric ``name``."""
    from bench.cells import by_name

    return by_name("metrics", name, root).read


class Run:
    """What a per-layer reader reads: the cell's counters and host spans,
    its window, and the trace summary of a traced run with the directory
    its trace is in."""

    def __init__(self, workload, cell, spans, summary, trace_dir):
        self.workload, self.cell, self.spans = workload, cell, spans
        self.counters, self.window_s = cell.counters, cell.window_s
        self.summary, self.trace_dir = summary, trace_dir
        self.scopes = None      # bench/scopes.py's summary, once read


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """The result record of one run of ``workload`` on this machine's chips;
    its set-up is counted from the start of the process."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell_entry, config, traffic = cell_spec(spec, workload)
    return measure(spec, cell_entry, config, traffic, seed, seconds, trace,
                   started=T0)


def measure(spec: dict, cell_entry: dict, config: dict, traffic: dict,
            seed: int, seconds: float, trace: bool,
            require_chip: bool = True, started: float = None,
            root: str = ROOT) -> dict:
    """Set up, measure, check and return the result record.
    ``require_chip=False`` skips the look for a chip (CPU rehearsals);
    ``root`` is the checkout whose kind, family and metric files are
    found by name.  A traced run writes its trace into a directory of its
    own, removed on return.  The compile-cache settings and the compile
    listener are the process's own again when it returns."""
    started = time.perf_counter() if started is None else started
    with own_trace_dir(trace) as trace_dir:
        return _measure(spec, cell_entry, config, traffic, seed, seconds,
                        trace_dir, require_chip, started, root)


@contextmanager
def own_trace_dir(trace: bool):
    """A new directory under ``.bench_trace/`` for a traced run (None for
    an untraced one), removed on exit: runs at once never share one."""
    if not trace:
        yield None
        return
    traces = os.path.join(ROOT, ".bench_trace")
    os.makedirs(traces, exist_ok=True)
    trace_dir = tempfile.mkdtemp(prefix="run-", dir=traces)
    try:
        yield trace_dir
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _measure(spec, cell_entry, config, traffic, seed, seconds, trace_dir,
             require_chip, started, root) -> dict:
    import jax

    from bench import cells as C, devices, trace as T

    workload, chips = cell_entry["name"], cell_entry["chips"]
    trace = trace_dir is not None
    if require_chip:
        device = devices.gate(chips)
    else:
        d0 = jax.devices()[0]
        device = dict(platform=d0.platform, kind=d0.device_kind,
                      count=len(jax.devices()))
    settings = dict(jax_compilation_cache_dir=os.environ.get(
        "JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache"),
        jax_persistent_cache_min_compile_time_secs=0,
        # the programs name their layers only in op metadata
        jax_compilation_cache_include_metadata_in_key=True)
    before = {k: getattr(jax.config, k) for k in settings}
    compiles = []

    def on_event(ev, secs, **_):
        if ev == COMPILE_EVENT:
            compiles.append(secs)

    for k, v in settings.items():
        jax.config.update(k, v)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        spans = C.Spans()
        cell = C.kind(traffic["kind"], root)(config, traffic, seed, spans,
                                             root=root)
        cell.setup()
        setup_s = time.perf_counter() - started
        n_compiles = len(compiles)
        spans.trace_dir = trace_dir
        cell.window(seconds)
        cell.counters["window_compiles"] = len(compiles) - n_compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        for k, v in before.items():
            jax.config.update(k, v)
    device["memory_peak_bytes"] = (devices.memory_peak_bytes(chips)
                                   if require_chip else 0)
    cell.take_answers()
    summary = None
    if trace:
        summary = T.summarize(T.load(trace_dir), chips)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    gaps, failed = cell.check()
    checks = {k: dict(value=v, limit=config["limits"][k])
              for k, v in gaps.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    e2e, layer = metric_sets(spec, workload)
    metrics = {}
    if trace:
        run = Run(workload, cell, spans, summary, trace_dir)
        for m in layer:
            v = reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        values = dict(cell.e2e(), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = dict(value=values[m["name"]], unit=m["unit"])
    out = dict(correct=correct, attempted=cell.attempted(), failed=failed,
               metrics=metrics, device=device)
    if trace:
        out["breakdown"] = T.breakdown(summary)
    out["checks"] = checks
    out["counters"] = dict(cell.counters, setup_s=setup_s,
                           window_s=cell.window_s,
                           call_s=[t1 - t0 for n, t0, t1 in spans.spans
                                   if n == "call"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: the system under test (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.devices import NoChip

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    counters = out.pop("counters")
    print("counters: " + json.dumps(counters), file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
