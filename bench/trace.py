"""Profiler traces: capture one window, reduce it to busy time, op time,
exposed collective time and labelled idle gaps.

``load`` turns JAX's ``.xplane.pb`` into a plain record that the reduction
and its test read:

    {"devices": {"/device:TPU:0": [[op name, start ns, duration ns], ...]},
     "host": [[span name, start ns, duration ns], ...]}

Device events are those of each TPU plane's "XLA Ops" line (all its
lines where it has none), named by their HLO instruction (``fusion.12``,
``while.3``, ``custom-call.5``); host events are the benchmark's own
``bench.*`` annotations.  Both share the profiler's clock.  Control-flow
ops (``while``, ``conditional``, ``call``) contain the ops they run: they
count towards busy time and not towards op time.
"""

from __future__ import annotations

import glob
import os
from contextlib import contextmanager
from typing import Dict, List, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
               "collective-permute", "allgather", "allreduce", "alltoall")
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``; a custom
    call keeps its target: ``custom-call.5:tpu_custom_call`` (a Pallas
    kernel compiled by Mosaic)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    target = text.partition('custom_call_target="')[2].partition('"')[0]
    return f"{name}:{target}" if target else name


def is_container(name: str) -> bool:
    return name.rsplit(".", 1)[0] in CONTAINERS


@contextmanager
def capture(out_dir: str):
    """Profile the body into ``out_dir``, which is the run's own: nothing
    in it is removed."""
    import jax

    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(out_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            devices[plane.name] = [[op_name(e.name), e.start_ns,
                                    e.duration_ns]
                                   for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.duration_ns]
                     for ln in plane.lines for e in ln.events
                     if e.name.startswith("bench.")]
    return dict(devices=devices, host=host)


def merge(ivs: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(ivs) -> float:
    return sum(e - s for s, e in ivs)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _device_order(name: str) -> int:
    tail = name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 1 << 30


def summarize(tr: dict, chips: int) -> dict:
    """Per-device busy time, op time and exposed collective time inside the
    traced window (the ``bench.window`` host span), in seconds, averaged
    over the first ``chips`` devices; the calls in the window; and chip 0's
    idle gaps, labelled by the innermost host span around their midpoint."""
    win = [(s, s + d) for n, s, d in tr["host"] if n == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0]
    spans = [(n[len("bench."):], s, s + d) for n, s, d in tr["host"]
             if n != "bench.window"]
    names = sorted(tr["devices"], key=_device_order)[:chips]
    busy, ops, collective, exposed, gaps = [], {}, [], [], []
    for i, dev in enumerate(names):
        evs = [(n, max(s, w0), min(s + d, w1)) for n, s, d in tr["devices"][dev]
               if s < w1 and s + d > w0]
        allv = merge([(s, e) for _, s, e in evs])
        busy.append(length(allv) / 1e9)
        leaf = [(n, s, e) for n, s, e in evs if not is_container(n)]
        for n, s, e in leaf:
            ops[n] = ops.get(n, 0.0) + (e - s) / 1e9 / len(names)
        coll = merge([(s, e) for n, s, e in leaf
                      if any(c in n.lower() for c in COLLECTIVES)])
        comp = merge([(s, e) for n, s, e in leaf
                      if not any(c in n.lower() for c in COLLECTIVES)])
        collective.append(length(coll) / 1e9)
        exposed.append(length(subtract(coll, comp)) / 1e9)
        if i == 0:
            for s, e in subtract([(w0, w1)], allv):
                mid = (s + e) / 2
                inner = [(t1 - t0, n) for n, t0, t1 in spans if t0 <= mid < t1]
                gaps.append([min(inner)[1] if inner else "window",
                             (e - s) / 1e9])
    n = max(len(names), 1)
    calls = sum(1 for name, s, _ in spans if name == "call" and w0 <= s < w1)
    return dict(window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / n, calls=calls,
                ops=ops, collective_s=sum(collective) / n,
                exposed_s=sum(exposed) / n, gaps=gaps, devices=len(names))


def idle_pct(summary: dict):
    """Percent of the window with no operation on the device; None when
    the trace holds no device."""
    if not summary["devices"]:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def op_share(summary: dict, patterns) -> float:
    """Share (0..1) of busy device time spent in ops whose name holds one
    of ``patterns``; None when no such op ran."""
    t = sum(v for k, v in summary["ops"].items()
            if any(p in k for p in patterns))
    if t <= 0 or summary["busy_s"] <= 0:
        return None
    return t / summary["busy_s"]


def breakdown(summary: dict) -> Dict[str, list]:
    top = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(summary["gaps"], key=lambda g: -g[1])[:10]
    return dict(device_ops=[[k, v] for k, v in top], idle_gaps=gaps)
