"""The program's own names in a profiler trace: the ``mwis.*`` scope of each
device op, and the program's ``mwis.*`` host spans.

``load`` turns the traced run's ``.xplane.pb`` into the plain record of
``bench/trace.py`` with one more field per device op, its op name (the
``jax.named_scope`` path the program put in the op's metadata, or "" where
the trace names none):

    {"devices": {"/device:TPU:0": [[op, start ns, duration ns, op name],
                                   ...]},
     "host": [[span name, start ns, duration ns], ...]}

The op name comes from the op's own stat on the device's "XLA Ops" line
where that stat names a ``mwis.`` scope; otherwise from the compiled
module's HLO, which the trace keeps in its ``/host:metadata`` plane: the
op's module is the "XLA Modules" event around it, and the instruction's
``metadata={op_name=...}`` names it (a fusion carries its root's, an
unnamed instruction its nearest named feeder's).  An op the compiler made
carries only the enclosing loop's name in its stat
(``jit(run)/shard_map/while``), which is why a stat with no scope in it
does not settle the name.  JAX's
``ProfileData`` shows neither metadata stats nor those HLO modules, so the
file is read here with a small protobuf reader.

``summarize`` reduces a record to what the per-layer readers need, inside
the traced window (the ``bench.window`` host span): each chip's leaf-op
time by op name (``while``, ``conditional``, ``call`` and ``cond`` ops
contain the ops they run and are not leaves), each chip's busy intervals,
the program's host spans and the calls in the window.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace

CONTAINERS = trace.CONTAINERS + ("cond",)
#: the stat of a device op's metadata that holds its op name (a v5e trace
#: carries it: ``jit(f)/while/body/mwis.aggregate/gather:``)
OP_NAME_STATS = ("tf_op",)
HOST_PREFIXES = ("bench.", "mwis.")
SCOPE = re.compile(r"mwis\.[\w.]+")


# ------------------------------------------------------------------ #
# protobuf wire format (only what an XSpace and an HloProto need)
# ------------------------------------------------------------------ #
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varint and fixed
    fields, bytes for length-delimited ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def _map(b: bytes):
    """A map<int64, message> entry: (key, value bytes)."""
    key, val = 0, b""
    for num, v in fields(b):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            val = v
    return key, val


class Plane:
    """One XPlane: its name, the lines asked for, its event and stat
    metadata."""

    def __init__(self, b: bytes, want_line):
        self.name, self.lines, raw_lines = "", {}, []
        self.event_meta: Dict[int, Tuple[str, list]] = {}
        self.stat_names: Dict[int, str] = {}
        for num, v in fields(b):
            if num == 2:
                self.name = v.decode()
            elif num == 3:
                raw_lines.append(v)
            elif num == 4:
                k, m = _map(v)
                name, stats = "", []
                for fnum, fv in fields(m):
                    if fnum == 2:
                        name = fv.decode(errors="replace")
                    elif fnum == 5:
                        stats.append(fv)
                self.event_meta[k] = (name, stats)
            elif num == 5:
                k, m = _map(v)
                self.stat_names[k] = next(
                    (fv.decode(errors="replace") for fnum, fv in fields(m)
                     if fnum == 2), "")
        for raw in raw_lines:
            name, ts, evs = None, 0, []
            for num, v in fields(raw):
                if num == 2:
                    name = v.decode()
                    if not want_line(self.name, name):
                        break
                elif num == 3:
                    ts = _signed(v)
                elif num == 4:
                    evs.append(v)
            else:
                if name is not None and want_line(self.name, name):
                    self.lines[name] = [_event(e, ts) for e in evs]

    def stat(self, raw: bytes):
        """(name, value) of one XStat of this plane."""
        name, val = "", None
        for num, v in fields(raw):
            if num == 1:
                name = self.stat_names.get(v, "")
            elif num in (5, 6):
                val = v.decode(errors="replace") if num == 5 else v
            elif num == 7:
                val = self.stat_names.get(v, "")
            elif num in (3, 4):
                val = _signed(v)
        return name, val

    def meta_stats(self, mid: int) -> Dict[str, object]:
        """The stats of one event metadata entry, by stat name."""
        raw = self.event_meta.get(mid, ("", []))[1]
        return dict(self.stat(s) for s in raw)


def _event(b: bytes, line_ts_ns: int):
    """(metadata id, start ns, duration ns) of one XEvent (its varint
    fields read inline: a trace holds millions of events)."""
    got = {1: 0, 2: 0, 3: 0}
    i, n = 0, len(b)
    while i < n:
        key = b[i]
        i += 1
        if key & 7:     # length-delimited (its stats): skip
            ln, i = _varint(b, i)
            i += ln
            continue
        x = shift = 0
        while True:
            c = b[i]
            i += 1
            x |= (c & 0x7F) << shift
            if c < 0x80:
                break
            shift += 7
        got[key >> 3] = x
    return got[1], line_ts_ns + got[2] / 1e3, got[3] / 1e3


def read_space(path: str, want_line) -> List[Plane]:
    with open(path, "rb") as f:
        b = f.read()
    return [Plane(v, want_line) for num, v in fields(b) if num == 1]


# ------------------------------------------------------------------ #
# HLO op names
# ------------------------------------------------------------------ #
def hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """Instruction name -> metadata op_name of a serialized HloProto.  A
    fusion takes the op_name of its fused computation's root; where the
    root carries none (a nested fusion, a tuple), the nearest named
    instruction that feeds the root.  Any other instruction with no
    op_name takes its nearest named feeder's: a collective the compiler
    made out of scoped ops (an all-gather lowered to a dynamic-update-slice
    and an all-reduce) then lands in their scope."""
    ins_by_id, root_of, named = {}, {}, []
    module = next((v for num, v in fields(hlo_proto) if num == 1), b"")
    for num, comp in fields(module):
        if num != 3:
            continue
        cid = root = None
        for cnum, v in fields(comp):
            if cnum == 2:
                name, opcode, op, iid, called, args = "", "", "", None, [], []
                for inum, x in fields(v):
                    if inum == 1:
                        name = x.decode()
                    elif inum == 2:
                        opcode = x.decode()
                    elif inum == 7:
                        op = next((m.decode() for mnum, m in fields(x)
                                   if mnum == 2), "")
                    elif inum == 35:
                        iid = x
                    elif inum in (36, 38):
                        ids = _packed(x) if isinstance(x, bytes) else [x]
                        (args if inum == 36 else called).extend(ids)
                ins_by_id[iid] = (op, opcode, called, args)
                named.append((name, iid))
            elif cnum == 5:
                cid = v
            elif cnum == 6:
                root = v
        root_of[cid] = root

    def resolve(iid, depth=0):
        op, opcode, called, _ = ins_by_id.get(iid, ("", "", [], []))
        if opcode == "fusion" and called and depth < 8:
            return nearest(root_of.get(called[0]), depth + 1) or op
        return op

    def nearest(root, depth):
        # breadth first from the root, through the operands
        queue, seen = [root], {root}
        for iid in queue:
            op = resolve(iid, depth)
            if op:
                return op
            for a in ins_by_id.get(iid, ("", "", [], []))[3]:
                if a not in seen:
                    seen.add(a)
                    queue.append(a)
        return ""

    return {name: resolve(iid) or nearest(iid, 0) for name, iid in named}


def _packed(b: bytes) -> List[int]:
    out, i = [], 0
    while i < len(b):
        x, i = _varint(b, i)
        out.append(x)
    return out


def _want(plane: str, line: str) -> bool:
    if plane.startswith("/device:TPU:"):
        return line in ("XLA Ops", "XLA Modules")
    return plane.startswith("/host:") and plane != "/host:metadata"


def load(out_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    planes = read_space(paths[-1], _want)
    modules: Dict[str, Dict[str, str]] = {}
    for pl in planes:
        if pl.name == "/host:metadata":
            for mid, (name, _) in pl.event_meta.items():
                proto = pl.meta_stats(mid).get("Hlo Proto")
                if isinstance(proto, bytes):
                    modules[name] = hlo_op_names(proto)
    devices, host = {}, []
    for pl in planes:
        if pl.name.startswith("/device:TPU:"):
            devices[pl.name] = device_ops(pl, modules)
        elif pl.name.startswith("/host:"):
            for line in pl.lines.values():
                for mid, s, d in line:
                    name = pl.event_meta.get(mid, ("", []))[0]
                    if name.startswith(HOST_PREFIXES):
                        host.append([name, s, d])
    return dict(devices=devices, host=host)


def device_ops(pl: Plane, modules: Dict[str, Dict[str, str]]) -> list:
    """[op, start, duration, op name] of a TPU plane's XLA Ops line."""
    mods = sorted((s, s + d, pl.event_meta.get(mid, ("", []))[0])
                  for mid, s, d in pl.lines.get("XLA Modules", ()))
    out, j, cache = [], 0, {}
    for mid, s, d in sorted(pl.lines.get("XLA Ops", ()),
                            key=lambda e: e[1]):
        if mid not in cache:
            stats = pl.meta_stats(mid)
            cache[mid] = (trace.op_name(pl.event_meta.get(mid, ("", []))[0]),
                          next((str(stats[k]) for k in OP_NAME_STATS
                                if stats.get(k)), None))
        op, scope = cache[mid]
        if not scope or not SCOPE.search(scope):
            while j < len(mods) and mods[j][1] < s:
                j += 1
            hlo = ""
            if j < len(mods) and mods[j][0] <= s:
                hlo = modules.get(mods[j][2], {}).get(op.split(":")[0], "")
            if not scope or SCOPE.search(hlo):
                scope = hlo
        out.append([op, s, d, scope])
    return out


# ------------------------------------------------------------------ #
# reduction
# ------------------------------------------------------------------ #
def is_container(op: str) -> bool:
    return op.rsplit(".", 1)[0] in CONTAINERS


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The program's scopes in an op name, outermost first, also where a
    transformation wraps them: ``jit(f)/while/body/mwis.rule.heavy/cond/..``
    -> ("mwis.rule.heavy",); ``jit(one)/vmap(mwis.reconstruct)/while`` ->
    ("mwis.reconstruct",)."""
    return tuple(SCOPE.findall(op_name))


def summarize(rec: dict, chips: int) -> dict:
    """Per chip, inside the traced window: leaf-op seconds by scope path
    and in all, and busy intervals; the window, its calls and the host
    spans that overlap it."""
    win = [(s, s + d) for n, s, d in rec["host"] if n == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0]
    names = sorted(rec["devices"], key=trace._device_order)[:chips]
    per_chip = []
    for dev in names:
        by_path: Dict[Tuple[str, ...], float] = {}
        busy = []
        for op, s, d, scope in rec["devices"][dev]:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            busy.append((s0, s1))
            if not is_container(op):
                p = scope_path(scope)
                by_path[p] = by_path.get(p, 0.0) + (s1 - s0) / 1e9
        per_chip.append(dict(by_path=by_path, leaf_s=sum(by_path.values()),
                             busy=trace.merge(busy)))
    spans = [(n, s, s + d) for n, s, d in rec["host"]
             if n != "bench.window" and s < w1 and s + d > w0]
    calls = sum(1 for n, s, _ in spans if n == "bench.call" and s >= w0)
    return dict(window=(w0, w1), chips=per_chip, spans=spans, calls=calls)


def under_s(summary: dict, prefix: str) -> float:
    """Leaf seconds under scopes that start with ``prefix``, averaged over
    the chips."""
    chips = summary["chips"]
    t = sum(v for c in chips for p, v in c["by_path"].items()
            if any(x.startswith(prefix) for x in p))
    return t / max(len(chips), 1)


def leaf_s(summary: dict) -> float:
    chips = summary["chips"]
    return sum(c["leaf_s"] for c in chips) / max(len(chips), 1)


def share_pct(summary: dict, prefix: str) -> Optional[float]:
    """Percent of leaf-op time under ``prefix``; None where the trace
    names no op so (a program without the scope)."""
    t, all_s = under_s(summary, prefix), leaf_s(summary)
    return 100.0 * t / all_s if t > 0 and all_s > 0 else None


def unscoped_pct(summary: dict) -> Optional[float]:
    """Percent of leaf-op time that carries no ``mwis.`` scope."""
    chips = summary["chips"]
    t = sum(c["by_path"].get((), 0.0) for c in chips) / max(len(chips), 1)
    all_s = leaf_s(summary)
    return 100.0 * t / all_s if all_s > 0 else None


def idle_within_pct(summary: dict, span: str) -> Optional[float]:
    """Percent of the window in which chip 0 ran no op while a host span
    named ``span`` was open; None without such a span."""
    w0, w1 = summary["window"]
    open_ = trace.merge([(max(s, w0), min(e, w1))
                         for n, s, e in summary["spans"] if n == span])
    if not open_ or not summary["chips"]:
        return None
    idle = trace.subtract([(w0, w1)], summary["chips"][0]["busy"])
    both = trace.subtract(open_, trace.subtract(open_, idle))
    return 100.0 * trace.length(both) / (w1 - w0)


def self_s(summary: dict, span: str) -> Optional[float]:
    """Seconds of the ``span`` host spans in the window less the parts
    that other program spans nested inside them cover."""
    w0, w1 = summary["window"]
    mine = [(s, e) for n, s, e in summary["spans"] if n == span]
    if not mine:
        return None
    total = 0.0
    for s, e in mine:
        inner = trace.merge([(a, b) for n, a, b in summary["spans"]
                             if n.startswith("mwis.") and (a, b) != (s, e)
                             and s <= a and b <= e])
        cut = trace.subtract([(max(s, w0), min(e, w1))], inner)
        total += trace.length(cut)
    return total / 1e9


def of_run(run) -> Optional[dict]:
    """The scope summary of a traced run (read from its own trace
    directory once, then kept on the run); None for an untraced run."""
    if not run.summary:
        return None
    if run.scopes is None:
        run.scopes = summarize(load(run.trace_dir), run.summary["devices"])
    return run.scopes
