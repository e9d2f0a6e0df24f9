"""The program's scopes and host spans read back from a trace: the reduction
on hand-made records, the reader on hand-made TPU-shaped trace files (op
names from an op stat, or from the module's HLO), and a recorded v5e
slice."""

import functools
import json
import os

import pytest

from bench import scopes as S
from bench.tests.helpers import child_result, measure_small, start_child

DATA = os.path.join(os.path.dirname(__file__), "data")


def ms(x):
    return int(x * 1e6)


def hand_record():
    """Window 0-100 ms.  Chip 0: a rule fusion 10-30, a heavy-vertex cond
    40-60 holding a fusion 40-55 (vmapped: the scope sits in ``vmap(..)``),
    an exchange all-gather 60-70, an unscoped copy 70-75.  Chip 1: a rule
    fusion 0-20 and an exchange op 20-60.  Host: call 0-100, the reduce
    pack 0-10 with its plan 0-6 nested, and a serve solve 10-100."""
    jit = "jit(_disredu_union_jit)/while/body"
    return dict(
        devices={
            "/device:TPU:0": [
                ["fusion.1", ms(10), ms(20),
                 f"{jit}/mwis.rule.degree_one/add"],
                ["cond.3", ms(40), ms(20), f"{jit}/mwis.rule.heavy/cond"],
                ["fusion.4", ms(40), ms(15),
                 f"{jit}/vmap(mwis.rule.heavy)/cond/branch_0_fun/dot"],
                ["all-gather.2", ms(60), ms(10), f"{jit}/mwis.exchange/ag"],
                ["copy.5", ms(70), ms(5), ""]],
            "/device:TPU:1": [
                ["fusion.1", ms(0), ms(20), f"{jit}/mwis.rule.simplicial/x"],
                ["fusion.9", ms(20), ms(40), f"{jit}/mwis.exchange/gather"]],
        },
        host=[["bench.window", ms(0), ms(100)], ["bench.call", ms(0), ms(100)],
              ["mwis.reduce.pack", ms(0), ms(10)],
              ["mwis.reduce.plan", ms(0), ms(6)],
              ["mwis.serve.solve", ms(10), ms(90)]])


def test_leaf_time_by_scope_averaged_over_chips():
    s = S.summarize(hand_record(), chips=2)
    # leaf ops: chip 0 has 20 + 15 + 10 + 5 = 50 ms (the cond contains the
    # fusion and is not a leaf), chip 1 has 60 ms
    assert S.leaf_s(s) == pytest.approx(0.055)
    assert S.under_s(s, "mwis.rule.") == pytest.approx((0.035 + 0.02) / 2)
    assert S.share_pct(s, "mwis.rule.heavy") == pytest.approx(
        100 * 0.0075 / 0.055)
    assert S.share_pct(s, "mwis.exchange") == pytest.approx(
        100 * 0.025 / 0.055)
    assert S.unscoped_pct(s) == pytest.approx(100 * 0.0025 / 0.055)
    assert S.share_pct(s, "mwis.peel") is None
    assert s["calls"] == 1


def test_idle_under_a_host_span_and_self_time_of_nested_spans():
    s = S.summarize(hand_record(), chips=1)
    # chip 0 idles 0-10 (all inside the pack span), 30-40 and 75-100
    assert S.idle_within_pct(s, "mwis.reduce.pack") == pytest.approx(10.0)
    assert S.idle_within_pct(s, "mwis.serve.pack") is None
    # pack lasts 10 ms, its nested plan 6 ms
    assert S.self_s(s, "mwis.reduce.pack") == pytest.approx(0.004)
    assert S.self_s(s, "mwis.serve.solve") == pytest.approx(0.09)


def test_scope_paths():
    assert S.scope_path("jit(f)/while/body/mwis.rule.heavy/cond/add") == (
        "mwis.rule.heavy",)
    assert S.scope_path("jit(one)/vmap(mwis.reconstruct)/while") == (
        "mwis.reconstruct",)
    assert S.scope_path("jit(f)/mwis.peel/mwis.aggregate/x") == (
        "mwis.peel", "mwis.aggregate")
    assert S.scope_path("jit(f)/while/body/add") == ()
    assert S.is_container("cond.19") and S.is_container("while.86")
    assert not S.is_container("fusion.550")
    assert not S.is_container("copy-start.9")


# --------------------------------------------------------------------- #
# hand-made trace files in the profiler's format
# --------------------------------------------------------------------- #
def varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def msg(*fields):
    """A protobuf message from (number, value) pairs: ints as varints,
    str/bytes length-delimited."""
    out = []
    for num, v in fields:
        if isinstance(v, int):
            out += [varint(num << 3), varint(v)]
        else:
            v = v.encode() if isinstance(v, str) else v
            out += [varint(num << 3 | 2), varint(len(v)), v]
    return b"".join(out)


def hlo_proto():
    """An HloProto of module ``jit_f``: a fusion whose own metadata is empty
    and whose fused root, a tuple, names nothing but is fed by a scoped
    multiply; and a scoped add."""
    def ins(name, iid, opcode, op="", called=(), args=()):
        f = [(1, name), (2, opcode), (35, iid)]
        if op:
            f.append((7, msg((2, op))))
        if args:
            f.append((36, b"".join(varint(a) for a in args)))
        if called:
            f.append((38, b"".join(varint(c) for c in called)))
        return msg(*f)

    fused = msg((1, "fused_computation"),
                (2, ins("mul.0", 10, "multiply", "jit(f)/mwis.aggregate/mul")),
                (2, ins("tuple.1", 11, "tuple", args=(10,))),
                (5, 2), (6, 11))
    main = msg((1, "main"), (2, ins("fusion.1", 1, "fusion", called=(2,))),
               (2, ins("add.2", 2, "add", "jit(f)/mwis.exchange/add")),
               (5, 1), (6, 2))
    return msg((1, msg((1, "jit_f"), (3, fused), (3, main))))


def plane(name, lines, event_meta, stat_names):
    f = [(2, name)]
    for lname, ts, events in lines:
        f.append((3, msg((2, lname), (3, ts),
                         *[(4, msg((1, mid), (2, off), (3, dur)))
                           for mid, off, dur in events])))
    for mid, (ename, stats) in event_meta.items():
        f.append((4, msg((1, mid), (2, msg((1, mid), (2, ename),
                                            *[(5, msg((1, sid), (k, v)))
                                              for sid, k, v in stats])))))
    for sid, sname in stat_names.items():
        f.append((5, msg((1, sid), (2, msg((1, sid), (2, sname))))))
    return msg(*f)


def write_space(path, op_stat: bool):
    """One chip running module jit_f 0-100 us: fusion.1 0-40 us, add.2
    50-80 us; host spans around it.  With ``op_stat`` each op's metadata
    names its op in a ``tf_op`` stat and no HLO module is kept."""
    ps = 1_000_000     # 1 us in ps
    meta = {1: ("jit_f(42)", []),
            2: ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
                [(1, 5, "jit(f)/mwis.rule.simplicial/and")] if op_stat
                else []),
            3: ("%add.2 = s32[8]{0} add(s32[8]{0} %a, s32[8]{0} %b)",
                [(1, 5, "jit(f)/mwis.exchange/add")] if op_stat else [])}
    device = plane("/device:TPU:0", [
        ("XLA Modules", 1000, [(1, 0, 100 * ps)]),
        ("XLA Ops", 1000, [(2, 0, 40 * ps), (3, 50 * ps, 30 * ps)])],
        meta, {1: "tf_op"})
    host = plane("/host:CPU", [("python", 900, [
        (1, 0, 300 * ps), (2, 50 * ps, 150 * ps), (3, 60 * ps, 20 * ps),
        (4, 0, 1000)])],
        {1: ("bench.window", []), 2: ("bench.call", []),
         3: ("mwis.reduce.pack", []), 4: ("$cells.py:1 window", [])}, {})
    planes = [device, host]
    if not op_stat:
        planes.append(plane("/host:metadata", [], {
            1: ("jit_f(42)", [(1, 6, hlo_proto())])}, {1: "Hlo Proto"}))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(msg(*[(1, p) for p in planes]))


@pytest.mark.parametrize("op_stat", [True, False],
                         ids=["op-stat", "module-hlo"])
def test_reader_names_device_ops(tmp_path, op_stat):
    path = tmp_path / "plugins" / "profile" / "x" / "h.xplane.pb"
    write_space(str(path), op_stat)
    rec = S.load(str(tmp_path))
    ops = rec["devices"]["/device:TPU:0"]
    want = (["jit(f)/mwis.rule.simplicial/and", "jit(f)/mwis.exchange/add"]
            if op_stat else ["jit(f)/mwis.aggregate/mul",
                             "jit(f)/mwis.exchange/add"])
    assert [o[0] for o in ops] == ["fusion.1", "add.2"]
    assert [o[3] for o in ops] == want
    assert [o[1:3] for o in ops] == [[1000.0, 40000.0], [51000.0, 30000.0]]
    assert sorted(h[0] for h in rec["host"]) == [
        "bench.call", "bench.window", "mwis.reduce.pack"]


def collective_space(path):
    """One chip running module jit_run as the v5e compiles a shard_map
    board all-gather: a dynamic-update-slice of the scoped board fusion,
    then an all-reduce; neither carries op metadata, and the all-reduce's
    ``tf_op`` stat names only the enclosing loop.  A copy's stat names no
    scope and neither does its HLO."""
    def ins(name, iid, opcode, op="", args=()):
        f = [(1, name), (2, opcode), (35, iid)]
        if op:
            f.append((7, msg((2, op))))
        if args:
            f.append((36, b"".join(varint(a) for a in args)))
        return msg(*f)

    board = "jit(run)/shard_map/while/body/mwis.exchange/gather"
    main = msg((1, "main"),
               (2, ins("fusion.1", 1, "fusion", board)),
               (2, ins("broadcast.2", 2, "broadcast")),
               (2, ins("dynamic-update-slice.3", 3, "dynamic-update-slice",
                       args=(2, 1))),
               (2, ins("bitcast.4", 4, "bitcast", args=(3,))),
               (2, ins("all-reduce.5", 5, "all-reduce", args=(4,))),
               (2, ins("copy.6", 6, "copy", "jit(run)/shard_map")),
               (5, 1), (6, 5))
    hlo = msg((1, msg((1, "jit_run"), (3, main))))
    ps = 1_000_000
    loop = "jit(run)/shard_map/while:"
    meta = {1: ("jit_run(7)", []),
            2: ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
                [(1, 5, board + ":")]),
            3: ("%all-reduce.5 = s32[32]{0} all-reduce(s32[32]{0} %b)",
                [(1, 5, loop)]),
            4: ("%copy.6 = s32[8]{0} copy(s32[8]{0} %c)", [(1, 5, loop)])}
    device = plane("/device:TPU:0", [
        ("XLA Modules", 0, [(1, 0, 100 * ps)]),
        ("XLA Ops", 0, [(2, 0, 10 * ps), (3, 10 * ps, 60 * ps),
                        (4, 70 * ps, 5 * ps)])],
        meta, {1: "tf_op"})
    md = plane("/host:metadata", [], {1: ("jit_run(7)", [(1, 6, hlo)])},
               {1: "Hlo Proto"})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(msg((1, device), (1, md)))


def test_reader_puts_a_lowered_collective_in_its_feeders_scope(tmp_path):
    path = tmp_path / "plugins" / "profile" / "x" / "h.xplane.pb"
    collective_space(str(path))
    ops = S.load(str(tmp_path))["devices"]["/device:TPU:0"]
    board = "jit(run)/shard_map/while/body/mwis.exchange/gather"
    assert [(o[0], o[3]) for o in ops] == [
        ("fusion.1", board + ":"), ("all-reduce.5", board),
        ("copy.6", "jit(run)/shard_map/while:")]


def test_reader_agrees_with_the_profilers_own():
    """The hand-made file is what JAX's reader reads too."""
    import tempfile

    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "h.xplane.pb")
        write_space(path, op_stat=False)
        data = ProfileData.from_file(path)
        theirs = sorted((e.name, e.start_ns, e.duration_ns)
                        for p in data.planes for ln in p.lines
                        for e in ln.events if e.name.startswith("mwis."))
        ours = sorted(tuple(h) for h in S.load(d)["host"]
                      if h[0].startswith("mwis."))
    assert ours == theirs


def test_recorded_v5e_slice():
    """600 ops of gnm11.rnp's traced solve on a v5e, named by each op's
    own ``tf_op`` stat: a heavy-vertex ``cond`` event spans the branch ops
    it runs, so it is a container and only its branch ops count."""
    with open(os.path.join(DATA, "trace_scopes_v5e.json")) as f:
        plain = json.load(f)
    # stored as bench/trace.py's plain record (so its reduction reads it
    # too), with each op's name beside it
    rec = dict(host=plain["host"], devices={
        dev: [e + [name] for e, name in zip(evs, plain["op_names"][dev])]
        for dev, evs in plain["devices"].items()})
    (ops,) = rec["devices"].values()
    (cond,) = [o for o in ops if o[0].startswith("cond.")]
    branch = [o for o in ops if o is not cond and cond[1] <= o[1]
              and o[1] + o[2] <= cond[1] + cond[2] + 1]
    assert len(branch) > 100
    assert sum(o[2] for o in branch) == pytest.approx(cond[2], rel=1e-3)
    s = S.summarize(rec, chips=1)
    busy = sum(o[2] for o in ops if not S.is_container(o[0])) / 1e9
    assert S.leaf_s(s) == pytest.approx(busy)
    assert S.under_s(s, "mwis.rule.heavy") == pytest.approx(
        sum(o[2] for o in branch if "mwis.rule.heavy" in o[3]) / 1e9)
    assert S.share_pct(s, "mwis.rule.") > 50
    assert S.share_pct(s, "mwis.aggregate") > 0
    assert S.unscoped_pct(s) < 1


@functools.lru_cache(maxsize=None)
def traced_serve():
    return measure_small("serve.mix16", trace=True)


def test_serve_pack_metric_on_a_traced_cpu_run():
    out = traced_serve()
    assert out["correct"]
    v = out["metrics"]["pack_ms_per_inst.serve"]
    assert v["unit"] == "ms" and v["value"] > 0


def test_serve_verify_metric_on_a_traced_cpu_run():
    out = traced_serve()
    assert out["correct"]
    v = out["metrics"]["verify_ms_per_inst.serve"]
    assert v["unit"] == "ms" and v["value"] > 0


#: a child's traced run, held where it has opened its trace until the
#: other child has too, and before it reads its trace until the other
#: child's trace is written too
AT_ONCE = """
import json, os, time
from contextlib import contextmanager
from bench import trace
from bench.tests.helpers import measure_small

capture, load = trace.capture, trace.load


def wait(stage):
    d = os.path.join({barrier!r}, stage)
    os.makedirs(d, exist_ok=True)
    open(os.path.join(d, {workload!r}), "w").close()
    t0 = time.time()
    while len(os.listdir(d)) < 2:
        assert time.time() - t0 < 600, "the other run never got to " + stage
        time.sleep(0.05)


@contextmanager
def held(out_dir):
    with capture(out_dir):
        wait("open")
        yield


def loaded(out_dir):
    wait("written")
    return load(out_dir)


trace.capture, trace.load = held, loaded
out = measure_small({workload!r}, trace=True)
print(json.dumps(dict(correct=out["correct"], metrics=sorted(out["metrics"]),
                      window_s=out["device"]["window_s"],
                      call_s=out["counters"]["call_s"][0])))
"""


def test_two_traced_runs_at_once_read_their_own_traces(tmp_path):
    """Two processes trace their windows at the same time: each reads its
    own trace (its traced window is its own first call) and its own
    scopes."""
    children = {w: start_child(AT_ONCE.format(barrier=str(tmp_path),
                                              workload=w))
                for w in ("serve.mix16", "gnm11.rnp")}
    outs = {w: child_result(c, timeout=900) for w, c in children.items()}
    for out in outs.values():
        assert out["correct"]
        assert out["window_s"] == pytest.approx(out["call_s"], rel=0.02,
                                                abs=0.01)
    assert {"pack_ms_per_inst.serve", "verify_ms_per_inst.serve"} <= set(
        outs["serve.mix16"]["metrics"])
    assert "peel_ms.rnp" in outs["gnm11.rnp"]["metrics"]
