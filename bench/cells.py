"""Cell drivers: one per traffic ``kind``, each driving one public entry.

A driver builds its inputs from the traffic file and the run's seed, warms
every program its window will run, drives the entry for whole passes over
its inputs until the window has lasted ``--seconds``, and keeps the answers.
After the window it hands the answers to the plain reference.

Inputs are a fixed set named by the traffic file (``graph_seeds`` or
``pool_seed``); the run's seed orders them, so every run does the same work
and a window always ends on a whole pass.  Every answer of the window is
compared.

A traffic ``kind`` is found by name: one of ``KINDS`` below, else the
``Cell`` of ``bench/kinds/<kind>.py``.  A graph cell's configuration names
its graph ``family`` (``gnm`` where it names none), whose ``generate`` is in
``bench/families/<family>.py``.  So a new cell kind or graph family joins
the benchmark as a new file.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import time
from contextlib import ExitStack, contextmanager
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

from bench import graphs, reference as ref

#: the checkout whose ``bench/`` holds the files found by name
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def by_name(folder: str, name: str, root: str = ROOT):
    """The module ``bench/<folder>/<name>.py`` under ``root``."""
    path = os.path.join(root, "bench", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spans:
    """Host spans of the run, on the host clock and in the profiler trace.
    With ``trace_dir`` the window's first call is traced into it, as the
    traced window (``bench.window``)."""

    def __init__(self, trace_dir: str = None):
        self.spans: List[Tuple[str, float, float]] = []
        self.trace_dir = trace_dir

    @contextmanager
    def __call__(self, name: str):
        import jax

        from bench import trace

        traced = name == "call" and self.trace_dir is not None
        with ExitStack() as stack:
            if traced:
                stack.enter_context(trace.capture(self.trace_dir))
                stack.enter_context(
                    jax.profiler.TraceAnnotation("bench.window"))
                self.trace_dir = None
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
            self.spans.append((name, t0, time.perf_counter()))


def warm_weights(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Weights under which one sweep decides every vertex: 200 on a maximal
    independent set (first-fit by id), 1 elsewhere.  Same shapes as the
    real input, so the warm-up compiles or loads every program the window
    runs without paying a whole reduction."""
    n = indptr.shape[0] - 1
    free = np.ones(n, bool)
    w = np.ones(n, np.int32)
    for v in range(n):
        if free[v]:
            w[v] = 200
            free[indices[indptr[v]:indptr[v + 1]]] = False
    return w


def program_graph(g: graphs.Csr):
    from repro.core.graph import Graph

    indptr, indices, weights = g
    return Graph(indptr=indptr, indices=indices, weights=weights)


def disredu_config(config: dict, **kw):
    from repro.core.distributed import DisReduConfig

    return DisReduConfig(
        heavy_k=config["heavy_k"], use_heavy=config["use_heavy"],
        mode=config["mode"], stale_sweeps=config["stale_sweeps"],
        schedule=config["schedule"], backend=config["backend"],
        max_rounds=config["max_rounds"], r_blk=config["r_blk"], **kw)


def reference_reducer(g: graphs.Csr, p: int, config: dict, **kw):
    sweeps = 1_000_000 if config["mode"] == "sync" else config["stale_sweeps"]
    return ref.Reducer(*g, p, D=config["window_cap"],
                       Dc=config["common_cap"], heavy_k=config["heavy_k"],
                       use_heavy=config["use_heavy"], sweeps=sweeps,
                       max_rounds=config["max_rounds"], **kw)


class Cell:
    """Common shape of a cell kind; a subclass fills its phases: ``setup``,
    ``window``, ``take_answers``, ``check``, ``attempted``, ``e2e`` and
    ``control_answers`` (with ``make_inputs`` where the control needs
    it).  ``root`` is the checkout whose files it finds by name."""

    def __init__(self, config: dict, traffic: dict, seed: int, spans: Spans,
                 root: str = ROOT):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans, self.root = spans, root
        self.counters: Dict[str, float] = {}
        self.window_s = 0.0

    def run_window(self, seconds: float, one_pass) -> None:
        """Whole passes until ``seconds`` have gone by."""
        t0 = time.perf_counter()
        with self.spans("measure"):
            while True:
                one_pass()
                if time.perf_counter() - t0 >= seconds:
                    break
        self.window_s = time.perf_counter() - t0


class GraphCell(Cell):
    """Cells over a fixed set of graphs of the configuration's family
    (reduce, reduce_mesh, rnp)."""

    def make_inputs(self) -> None:
        """The fixed graphs and their order."""
        t = self.traffic
        generate = family(self.config.get("family", "gnm"), self.root)
        rng = graphs.rng_of(self.seed)
        self.order = [int(i) for i in rng.permutation(len(t["graph_seeds"]))]
        self.graphs = [generate(t["pes"] * t["n_per_pe"], self.config, s)
                       for s in t["graph_seeds"]]

    def build_graphs(self) -> None:
        t0 = time.perf_counter()
        with self.spans("host_build"):
            self.make_inputs()
            pad = self.pad_to()
            self.pgs = [self.partition(g, pad) for g in self.graphs]
        self.counters["host_build_s"] = time.perf_counter() - t0

    def pad_to(self):
        return None

    def partition(self, g, pad):
        from repro.core.partition import partition_graph

        return partition_graph(program_graph(g), self.traffic["pes"],
                               window_cap=self.config["window_cap"],
                               common_cap=self.config["common_cap"],
                               pad_to=pad)

    def warm_pg(self, k: int):
        pg = self.pgs[k]
        w = warm_weights(*self.graphs[k][:2])
        w0 = np.zeros_like(pg.w0)
        for i in range(pg.p):
            loc = pg.is_local[i] | pg.is_ghost[i]
            w0[i][loc] = w[pg.gid[i][loc]]
        return dataclasses.replace(pg, w0=w0)

    def new_window(self) -> None:
        """One list of answers per graph."""
        self.kept = [[] for _ in self.graphs]

    def check_each(self, compare) -> Tuple[dict, int]:
        """``compare(k, answers)`` for every graph, summed; a graph with no
        answer counts as unanswered."""
        gaps, failed = {}, 0
        for k, answers in enumerate(self.answers):
            g, f = compare(k, answers)
            for key, v in g.items():
                gaps[key] = gaps.get(key, 0) + v
            failed += f
        gaps["unanswered"] = sum(not a for a in self.answers)
        return gaps, failed + gaps["unanswered"]


def reduce_answer(status, w, offset, log_kind, log_v, log_u, log_n,
                  starts, n) -> dict:
    """The program's stacked [p, V] reduce outputs in global ids."""
    p = starts.shape[0] - 1
    out_s, out_w = np.empty(n, np.int64), np.empty(n, np.int64)
    logs = []
    for i in range(p):
        lo, hi = int(starts[i]), int(starts[i + 1])
        out_s[lo:hi] = status[i][:hi - lo]
        out_w[lo:hi] = w[i][:hi - lo]
        k = int(log_n[i])
        logs.append(np.stack([log_kind[i][:k], lo + log_v[i][:k],
                              lo + log_u[i][:k]], axis=1).astype(np.int64))
    return dict(status=out_s, w=out_w, offset=int(np.sum(offset)), log=logs)


def tally(per_answer: List[Dict[str, int]], keys) -> Tuple[dict, int]:
    """Summed gaps over answers, and how many answers had any."""
    gaps = {k: sum(a[k] for a in per_answer) for k in keys}
    return gaps, sum(any(a.values()) for a in per_answer)


def compare_reduce(answers: List[dict], want: dict) -> Tuple[dict, int]:
    """Exact comparison of reduced graphs with the reference's."""
    per = []
    for a in answers:
        log = 0
        for x, y in zip(a["log"], want["log"]):
            k = min(len(x), len(y))
            log += int((x[:k] != y[:k]).any(axis=1).sum()) + abs(len(x) - len(y))
        per.append(dict(
            status_mismatch=int((a["status"] != want["status"]).sum()),
            weight_mismatch=int((a["w"] != want["w"]).sum()),
            offset_gap=abs(a["offset"] - want["offset"]), log_mismatch=log))
    return tally(per, ("status_mismatch", "weight_mismatch", "offset_gap",
                       "log_mismatch"))


class ReduceCell(GraphCell):
    """DisReduA on the union path (``distributed.disredu``), one chip."""

    def setup(self) -> None:
        from repro.core import distributed as D

        self.cfg = disredu_config(self.config)
        self.build_graphs()
        with self.spans("warmup"):
            for k in range(len(self.pgs)):
                state, _, _ = D.disredu(self.warm_pg(k), self.cfg)
                state.w.block_until_ready()

    def window(self, seconds: float) -> None:
        from repro.core import distributed as D

        self.new_window()
        calls, rounds = 0, 0

        def one_pass():
            nonlocal calls, rounds
            for k in self.order:
                with self.spans("call"):
                    state, _, r = D.disredu(self.pgs[k], self.cfg)
                    state.w.block_until_ready()
                calls, rounds = calls + 1, rounds + r
                self.kept[k].append(state)

        self.run_window(seconds, one_pass)
        self.counters.update(calls=calls, rounds=rounds)

    def take_answers(self) -> None:
        self.answers = []
        for g, kept in zip(self.graphs, self.kept):
            starts = ref.block_starts(g[0], self.traffic["pes"])
            self.answers.append([reduce_answer(
                *(np.asarray(x)[None] for x in (s.status, s.w, s.offset,
                                                s.log_kind, s.log_v, s.log_u,
                                                s.log_n)),
                starts, g[2].shape[0]) for s in kept])
        self.kept = self.pgs = None

    def check(self):
        def compare(k, answers):
            r = reference_reducer(self.graphs[k], self.traffic["pes"],
                                  self.config)
            r.reduce()
            return compare_reduce(answers, r.result())

        return self.check_each(compare)

    def attempted(self) -> int:
        return self.counters["calls"]

    def control_answers(self) -> None:
        """The bfloat16 reference in the program's place."""
        self.answers = []
        for g in self.graphs:
            r = reference_reducer(g, self.traffic["pes"], self.config,
                                  lowp=True)
            r.reduce()
            self.answers.append([r.result()])
        self.counters["calls"] = len(self.graphs)

    def e2e(self) -> Dict[str, float]:
        return dict(reduce_s=self.window_s / self.counters["calls"])


class MeshReduceCell(ReduceCell):
    """DisReduA with one PE per chip (``disredu_shard_map_fn``)."""

    def pad_to(self):
        """One padded shape for every graph, so one program serves all."""
        return shape_floor(self.graphs, self.traffic["pes"])

    def setup(self) -> None:
        import jax

        from repro.core import distributed as D
        from repro.launch.mesh import make_host_mesh

        p = self.traffic["pes"]
        self.cfg = disredu_config(self.config,
                                  exchange=self.traffic["exchange"])
        self.build_graphs()
        mesh = make_host_mesh(p)
        self.exes, self.arrays = [], []
        with self.spans("warmup"):
            for pg in self.pgs:
                run, _ = D.disredu_shard_map_fn(pg, self.cfg, mesh)
                arrays = D.place_on_mesh(D.shard_map_arrays(pg, self.cfg),
                                         mesh)
                self.exes.append(jax.jit(run).lower(arrays).compile())
                self.arrays.append(arrays)

    def window(self, seconds: float) -> None:
        import jax

        self.new_window()
        calls, rounds = 0, 0

        def one_pass():
            nonlocal calls, rounds
            for k in self.order:
                with self.spans("call"):
                    out = jax.block_until_ready(self.exes[k](self.arrays[k]))
                calls, rounds = calls + 1, rounds + int(np.asarray(out[7])[0])
                self.kept[k].append(out)

        self.run_window(seconds, one_pass)
        self.counters.update(calls=calls, rounds=rounds)

    def take_answers(self) -> None:
        self.answers = []
        for g, kept in zip(self.graphs, self.kept):
            starts = ref.block_starts(g[0], self.traffic["pes"])
            answers = []
            for out in kept:
                w, status, kind, lv, lu, ln, offset, _ = (np.asarray(x)
                                                          for x in out)
                answers.append(reduce_answer(status, w, offset, kind, lv, lu,
                                             ln, starts, g[2].shape[0]))
            self.answers.append(answers)
        self.kept = self.pgs = self.exes = self.arrays = None


def shape_floor(gs: List[graphs.Csr], p: int) -> Dict[str, int]:
    """The largest per-PE sizes (locals, ghosts, directed edges, interface,
    pairwise halo) over graphs, as the partition pads them."""
    L = G = E = B = S = 0
    for indptr, indices, _ in gs:
        starts = ref.block_starts(indptr, p)
        owner = np.searchsorted(starts, np.arange(indptr.shape[0] - 1),
                                side="right") - 1
        src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
        cut = owner[src] != owner[indices]
        for i in range(p):
            mine = owner[src] == i
            L = max(L, int(starts[i + 1] - starts[i]))
            ghosts = np.unique(indices[mine & cut])
            G = max(G, ghosts.shape[0])
            E = max(E, int(mine.sum() + (mine & cut).sum()))
            B = max(B, np.unique(src[mine & cut]).shape[0])
            if ghosts.size:
                S = max(S, int(np.bincount(owner[ghosts]).max()))
    return dict(L=L, G=G, E=E, B=B, S=S)


MEMBER_GAPS = ("member_mismatch", "weight_gap", "conflicts")


def member_gaps(a: np.ndarray, want: np.ndarray, g: graphs.Csr,
                reported=None) -> Dict[str, int]:
    """One solution against the reference's: members that differ, the gap
    of its weight (as reported, or recomputed from the input), and edges of
    the input with both ends in it."""
    got = ref.set_weight(g[2], a) if reported is None else reported
    return dict(member_mismatch=int((a != want).sum()),
                weight_gap=abs(int(got) - ref.set_weight(g[2], want)),
                conflicts=ref.conflicts(g[0], g[1], a))


class RnpCell(GraphCell):
    """Reduce-and-peel through ``solvers.solve(pg, "rnp", cfg)``."""

    def setup(self) -> None:
        from repro.core import solvers as S

        self.cfg = disredu_config(self.config)
        self.build_graphs()
        with self.spans("warmup"):
            for k in range(len(self.pgs)):
                S.solve(self.warm_pg(k), "rnp", self.cfg)

    def window(self, seconds: float) -> None:
        from repro.core import solvers as S

        self.new_window()
        solves, peels, weight = 0, 0, 0

        def one_pass():
            nonlocal solves, peels, weight
            for k in self.order:
                with self.spans("call"):
                    members, state, it = S.solve(self.pgs[k], "rnp", self.cfg)
                solves, peels = solves + 1, peels + it
                weight += ref.set_weight(self.graphs[k][2], members)
                self.kept[k].append((members, state))

        self.run_window(seconds, one_pass)
        self.counters.update(solves=solves, peels=peels, weight=weight)

    def take_answers(self) -> None:
        self.answers = []
        for g, kept in zip(self.graphs, self.kept):
            n = g[2].shape[0]
            self.answers.append([dict(members=m,
                                      status=np.asarray(s.status)[:n],
                                      offset=int(s.offset))
                                 for m, s in kept])
        self.kept = self.pgs = None

    def check(self):
        def compare(k, answers):
            g = self.graphs[k]
            r = reference_reducer(g, self.traffic["pes"], self.config)
            r.reduce_and_peel()
            want, final = r.members(), r.result()
            per = [dict(member_gaps(a["members"], want, g),
                        status_mismatch=int(
                            (a["status"] != final["status"]).sum()),
                        offset_gap=abs(a["offset"] - final["offset"]))
                   for a in answers]
            return tally(per, MEMBER_GAPS + ("status_mismatch", "offset_gap"))

        return self.check_each(compare)

    def attempted(self) -> int:
        return self.counters["solves"]

    def control_answers(self) -> None:
        """The bfloat16 reference in the program's place."""
        self.answers = []
        for g in self.graphs:
            r = reference_reducer(g, self.traffic["pes"], self.config,
                                  lowp=True)
            r.reduce_and_peel()
            final = r.result()
            self.answers.append([dict(members=r.members(),
                                      status=final["status"],
                                      offset=final["offset"])])
        self.counters["solves"] = len(self.graphs)

    def e2e(self) -> Dict[str, float]:
        c = self.counters
        return dict(solve_s=self.window_s / c["solves"],
                    solve_weight=c["weight"] / c["solves"])


class Answer(NamedTuple):
    """A served answer as the reference's control gives it."""

    members: np.ndarray
    weight: int
    ok: bool = True


class ServeCell(Cell):
    """A closed loop of fixed batches through ``MWISService.solve_batch``."""

    def make_inputs(self) -> None:
        t, c = self.traffic, self.config
        self.reqs = graphs.serve_stream(
            c["cells"], t["batch"] * t["batches"], t["repeat"], t["n_frac"],
            t["pool_seed"], c["weight_lo"], c["weight_hi"])
        rng = graphs.rng_of(self.seed)
        self.order = [int(i) for i in rng.permutation(t["batches"])]

    def setup(self) -> None:
        from repro.core import serve as SV

        t, c = self.traffic, self.config
        n_req = t["batch"] * t["batches"]
        t0 = time.perf_counter()
        with self.spans("host_build"):
            self.make_inputs()
        self.counters["host_build_s"] = time.perf_counter() - t0
        self.svc = SV.MWISService(SV.ServeConfig(
            algo=c["algo"], backend=c["backend"], verify=c["verify"],
            heavy_k=c["heavy_k"], use_heavy=c["use_heavy"],
            max_rounds=c["max_rounds"]))
        cells = [(x.L, x.E, x.D, x.Dc) for x in self.svc.cells]
        want = [(x["L"], x["E"], x["window_cap"], x["common_cap"])
                for x in c["cells"]]
        if cells != want:
            raise RuntimeError(f"serve cells {cells} are not the "
                               f"configuration's {want}")
        b = t["batch"]
        self.batches = [[program_graph(g) for g in self.reqs[i:i + b]]
                        for i in range(0, n_req, b)]
        with self.spans("warmup"):
            for batch in self.batches:
                warm = [dataclasses.replace(g, weights=warm_weights(
                    g.indptr, g.indices)) for g in batch]
                errs = [r.reason for r in self.svc.solve_batch(warm)
                        if not r.ok]
                if errs:
                    raise RuntimeError(f"warm-up requests failed: {errs}")

    def window(self, seconds: float) -> None:
        b = self.traffic["batch"]
        self.kept: List[Tuple[int, list]] = []
        done = 0

        def one_pass():
            nonlocal done
            for k in self.order:
                with self.spans("call"):
                    res = self.svc.solve_batch(self.batches[k])
                done += sum(r.ok for r in res)
                self.kept.append((k * b, res))

        self.run_window(seconds, one_pass)
        st = self.svc.stats
        self.counters.update(
            instances=done, attempted=len(self.kept) * b, fallbacks=st["fallbacks"], backend_active=st["backend_active"])

    def take_answers(self) -> None:
        self.answers = [(i0 + j, r) for i0, res in self.kept
                        for j, r in enumerate(res)]
        self.kept = self.svc = self.batches = None

    def reference(self, g: graphs.Csr, lowp: bool = False) -> np.ndarray:
        c = self.config
        n, e = g[2].shape[0], g[1].shape[0]
        cell = next(x for x in c["cells"] if x["L"] >= n and x["E"] >= e)
        conf = dict(c, window_cap=cell["window_cap"],
                    common_cap=cell["common_cap"])
        r = reference_reducer(g, 1, conf, lowp=lowp)
        r.reduce_and_peel()
        return r.members()

    def check(self):
        want = [self.reference(g) for g in self.reqs]
        per, unanswered = [], self.counters["attempted"] - len(self.answers)
        for i, r in self.answers:
            if r.ok and r.members.shape == want[i].shape:
                per.append(member_gaps(r.members, want[i], self.reqs[i],
                                       r.weight))
            else:
                unanswered += 1
        gaps, failed = tally(per, MEMBER_GAPS)
        gaps["unanswered"] = unanswered
        return gaps, failed + unanswered

    def attempted(self) -> int:
        return self.counters["attempted"]

    def control_answers(self) -> None:
        """The bfloat16 reference in the program's place."""
        self.answers = []
        for i, g in enumerate(self.reqs):
            m = self.reference(g, lowp=True)
            self.answers.append((i, Answer(m, ref.set_weight(g[2], m))))
        self.counters["attempted"] = len(self.reqs)

    def e2e(self) -> Dict[str, float]:
        return dict(serve_inst_per_s=self.counters["instances"]
                    / self.window_s)


KINDS = {"reduce": ReduceCell, "reduce_mesh": MeshReduceCell,
         "rnp": RnpCell, "serve": ServeCell}


def kind(name: str, root: str = ROOT):
    """The cell class of the traffic kind ``name``: a built-in one, else the
    ``Cell`` of ``bench/kinds/<name>.py``."""
    return KINDS.get(name) or by_name("kinds", name, root).Cell


def family(name: str, root: str = ROOT):
    """``generate(n, config, seed) -> graphs.Csr`` of the graph family
    ``name``, from ``bench/families/<name>.py``."""
    return by_name("families", name, root).generate
