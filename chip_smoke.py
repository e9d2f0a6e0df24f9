"""Smoke run of the MWIS reducer and the batched service on TPU chips.

    python chip_smoke.py              # one chip: reduce, RnP solve, serving
    python chip_smoke.py --chips 4    # four chips: the one-PE-per-chip path

Everything runs in this one process: a chip belongs to one process, so
nothing here starts a child.  The run refuses to start unless JAX sees a
TPU and the Pallas kernels compile through Mosaic (no interpret mode).

One chip, three phases:

  reduce  GNM with m = 4n on p = 1: DisReduA with the cheap-fused schedule
          under backend=pallas and backend=jnp.  The two reduced graphs
          must be identical (status, weights, |V'|, |E'|, offset, rounds).
          The default n = 2^18 is two powers of two below the paper's
          per-core weak-scaling size (``--reduce-log2n 20``, the
          ``weak_1m`` cell): on a v5e one 2^20 reduce call takes about
          two minutes per backend, which with compilation and the other
          phases leaves too little of the smoke's 20-minute limit.
  solve   reduce-and-peel through ``solvers.solve`` under both backends:
          identical members, weight, offset and peel count, and an
          independent set.  RnP peels one vertex per PE per iteration,
          about 0.06 n iterations on GNM, each a full reduce: on a v5e
          2^14 vertices took 330 s per backend, so it runs at 2^13.
  serve   ``MWISService`` (pallas, rnp, verify=full) over batches of 16
          requests that cycle serve_xs/s/m with repeated topologies, as
          ``launch/serve.py`` generates them: every result ok, no backend
          fallback, no solve error or failed verification, and every
          launch donated its weight plane.

``--chips 4`` runs only the mesh phase: one PE per chip over a 4-device
``pe`` mesh with backend=pallas, allgather and a2a exchange, each against
the union path on the same partition (all four PEs in one program, jnp
backend, on the host CPU: independent of the kernel and the collectives
under test):

  reduce  ``distributed.disredu_shard_map_fn`` at 2^14 vertices per chip:
          final status, weights and offset bit-identical.  Compilation
          bounds the size: for a v5e one such program compiles in about
          50 s at 2^14 per chip, 85 s at 2^15 and 200-250 s at 2^16, so
          the phase's four programs compile in threads.
  rnp     ``solvers.solver_shard_map_fn`` at 2^8 vertices per chip: the
          same, plus the same independent-set weight.

The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
phase exits non-zero without printing it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: log2 of the per-PE vertex count of each phase (GNM, m = 4n).
REDUCE_LOG2N = 18
SOLVE_LOG2N = 13
MESH_REDUCE_LOG2N = 14
MESH_SOLVE_LOG2N = 8

SEED = 0
SERVE_BATCH = 16
SERVE_BATCHES = 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A phase's result broke its contract."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation (monitoring events)."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.total += secs


def device_gate(chips: int) -> dict:
    """The device the run will use, or SmokeFailure naming what was found."""
    import jax

    from repro.kernels import interpret_mode

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX runs on platform {d0.platform!r} "
            f"({d0.device_kind}, {len(devs)} device(s))")
    if interpret_mode():
        raise SmokeFailure("the Pallas kernels would run in interpret mode")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devs))


def gnm_partition(n: int, p: int, seed: int):
    from repro.core import partition as part
    from repro.graphs import generators as gen

    t0 = time.perf_counter()
    g = gen.FAMILIES["gnm"](n, seed=seed)
    t1 = time.perf_counter()
    pg = part.partition_graph(g, p)
    t2 = time.perf_counter()
    log(f"graph: gnm n={g.n} m={g.m} p={p} L={pg.L} E={pg.E} G={pg.G} "
        f"generate_s={t1 - t0:.3f} partition_s={t2 - t1:.3f}")
    return g, pg


def _cfg(backend: str, **kw):
    from repro.configs.base import MWIS_SHAPES
    from repro.core import distributed as D

    return D.DisReduConfig(
        mode="async", schedule="cheap-fused", backend=backend,
        r_blk=MWIS_SHAPES["weak_1m"]["seg_blk"]["r_blk"], **kw)


def phase_reduce(clock: CompileClock, log2n: int, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import distributed as D

    g, pg = gnm_partition(1 << log2n, 1, seed)
    out = {}
    for backend in ("pallas", "jnp"):
        cfg = _cfg(backend)
        c0, t0 = clock.total, time.perf_counter()
        state, _, rounds = D.disredu(pg, cfg)
        jax.block_until_ready(state)
        first_s, compile_s = time.perf_counter() - t0, clock.total - c0
        t0 = time.perf_counter()
        again, _, rounds2 = D.disredu(pg, cfg)
        jax.block_until_ready(again)
        steady_s = time.perf_counter() - t0
        res = dict(status=np.asarray(state.status), w=np.asarray(state.w),
                   offset=int(state.offset), rounds=rounds)
        check(np.array_equal(res["status"], np.asarray(again.status))
              and np.array_equal(res["w"], np.asarray(again.w))
              and rounds == rounds2,
              f"reduce/{backend}: two runs of one input differ")
        res["nv"], res["ne"] = D.kernel_stats(pg, state)
        out[backend] = res
        log(f"reduce/{backend}: rounds={rounds} |V'|={res['nv']} "
            f"|V'|/|V|={res['nv'] / g.n:.6f} |E'|={res['ne']} "
            f"|E'|/|E|={res['ne'] / g.m:.6f} offset={res['offset']} "
            f"compile_s={compile_s:.3f} first_call_s={first_s:.3f} "
            f"steady_call_s={steady_s:.3f}")
        del state, again
    a, b = out["pallas"], out["jnp"]
    for k in ("nv", "ne", "offset", "rounds"):
        check(a[k] == b[k], f"reduce: {k} pallas={a[k]} jnp={b[k]}")
    check(np.array_equal(a["status"], b["status"])
          and np.array_equal(a["w"], b["w"]),
          "reduce: pallas and jnp reduced graphs differ")
    log("reduce: pallas == jnp (status, w, |V'|, |E'|, offset, rounds)")


def phase_solve(clock: CompileClock, log2n: int, seed: int) -> None:
    import numpy as np

    from repro.core import solvers as S

    g, pg = gnm_partition(1 << log2n, 1, seed)
    out = {}
    for backend in ("pallas", "jnp"):
        c0, t0 = clock.total, time.perf_counter()
        members, state, peels = S.solve(pg, "rnp", _cfg(backend))
        wall_s, compile_s = time.perf_counter() - t0, clock.total - c0
        check(g.is_independent_set(members),
              f"solve/{backend}: RnP result is not an independent set")
        weight = int(g.set_weight(members))
        out[backend] = (members, weight, int(state.offset), peels)
        log(f"solve/{backend}: rnp weight={weight} |I|={int(members.sum())} "
            f"offset={int(state.offset)} peels={peels} "
            f"compile_s={compile_s:.3f} wall_s={wall_s:.3f}")
    (ma, wa, oa, pa), (mb, wb, ob, pb) = out["pallas"], out["jnp"]
    check(wa == wb and oa == ob and pa == pb and np.array_equal(ma, mb),
          f"solve: pallas (w={wa}, offset={oa}, peels={pa}) != "
          f"jnp (w={wb}, offset={ob}, peels={pb})")
    log("solve: pallas == jnp (members, weight, offset, peels), independent")


def phase_serve(clock: CompileClock, batches: int, seed: int) -> None:
    from repro.core import serve as SV
    from repro.launch.serve import mwis_requests

    donated = []

    class Service(SV.MWISService):
        """Records the weight plane each launch donates."""

        def _launch_chunk(self, staged):
            donated.append(staged.args[0])
            return super()._launch_chunk(staged)

    svc = Service(SV.ServeConfig(backend="pallas", verify="full",
                                 algo="rnp"))
    reqs = mwis_requests(svc.cells, SERVE_BATCH * batches,
                         repeat_topologies=4, seed=seed)
    for rnd in ("first", "steady"):
        for i in range(batches):
            batch = reqs[i * SERVE_BATCH:(i + 1) * SERVE_BATCH]
            c0, t0 = clock.total, time.perf_counter()
            res = svc.solve_batch(batch)
            wall_s, compile_s = time.perf_counter() - t0, clock.total - c0
            bad = [(j, r.reason) for j, r in enumerate(res) if not r.ok]
            check(not bad, f"serve: failed requests {bad}")
            log(f"serve/{rnd}/batch{i}: requests={len(batch)} "
                f"weight={sum(r.weight for r in res)} "
                f"compile_s={compile_s:.3f} wall_s={wall_s:.3f}")
    st = svc.stats
    log("serve: " + " ".join(f"{k}={st[k]}" for k in (
        "backend_active", "fallbacks", "solve_errors", "verify_checked",
        "verify_failures", "programs", "cache_hits", "cache_misses")))
    check(st["backend_active"] == "pallas",
          f"serve: backend demoted to {st['backend_active']}")
    for k in ("fallbacks", "solve_errors", "pack_errors", "verify_failures"):
        check(st[k] == 0, f"serve: {k}={st[k]}")
    check(st["verify_checked"] == 2 * len(reqs),
          f"serve: verified {st['verify_checked']} of {2 * len(reqs)}")
    check(donated and all(w.is_deleted() for w in donated),
          "serve: a launch kept its weight plane (not donated)")
    log(f"serve: ok, {len(donated)} launches donated their weight plane")


def _mesh_members(pg, members):
    """Global member mask from the stacked [p, V] shard_map members."""
    import numpy as np

    glob = np.zeros(pg.n_global, dtype=bool)
    members = np.asarray(members)
    for i in range(pg.p):
        glob[pg.gid[i][members[i] & pg.is_local[i]]] = True
    return glob


def _compile_mesh(build, pg, cfg, mesh):
    """(executable, placed inputs, compile seconds) of one shard_map
    program; its inputs go straight to their PEs' chips."""
    import jax

    from repro.core import distributed as D

    run, _ = build(pg, cfg, mesh)
    arrays = D.place_on_mesh(D.shard_map_arrays(pg, cfg), mesh)
    t0 = time.perf_counter()
    exe = jax.jit(run).lower(arrays).compile()
    return exe, arrays, time.perf_counter() - t0


def phase_mesh(clock: CompileClock, reduce_log2n: int, solve_log2n: int,
               seed: int) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    from repro.core import distributed as D
    from repro.core import solvers as S
    from repro.launch.mesh import make_host_mesh

    p = 4
    mesh = make_host_mesh(p)
    cfg = _cfg("pallas")
    g_r, pg_r = gnm_partition(p << reduce_log2n, p, seed)
    # reduce-and-peel peels one vertex per PE per iteration: a small graph
    g_s, pg_s = gnm_partition(p << solve_log2n, p, seed)
    builds = {
        "reduce": (D.disredu_shard_map_fn, pg_r),
        "rnp": (lambda pg, c, m: S.solver_shard_map_fn(pg, c, m, "rnp"),
                pg_s),
    }

    # The one-PE-per-chip programs compile in threads while the host CPU
    # computes the union-path references (jnp backend): the same union
    # code, independent of the kernel and of the collectives under test.
    c0, t0 = clock.total, time.perf_counter()
    with ThreadPoolExecutor(4) as pool:
        futs = {
            (algo, ex): pool.submit(
                _compile_mesh, build, pg,
                dataclasses.replace(cfg, exchange=ex), mesh)
            for algo, (build, pg) in builds.items()
            for ex in ("allgather", "a2a")
        }
        with jax.default_device(jax.devices("cpu")[0]):
            t1 = time.perf_counter()
            state_r, _, rounds_r = D.disredu(pg_r, _cfg("jnp"))
            nv, ne = D.kernel_stats(pg_r, state_r)
            log(f"mesh/reduce/union: rounds={rounds_r} "
                f"|V'|/|V|={nv / g_r.n:.6f} |E'|/|E|={ne / g_r.m:.6f} "
                f"offset={int(state_r.offset)} "
                f"host_s={time.perf_counter() - t1:.3f}")
            t1 = time.perf_counter()
            members_s, state_s, peels_s = S.solve(pg_s, "rnp", _cfg("jnp"))
            weight_s = int(g_s.set_weight(members_s))
            log(f"mesh/rnp/union: weight={weight_s} "
                f"offset={int(state_s.offset)} peels={peels_s} "
                f"host_s={time.perf_counter() - t1:.3f}")
        compiled = {k: f.result() for k, f in futs.items()}
    log(f"mesh: compiled {len(compiled)} programs in "
        f"{time.perf_counter() - t0:.3f} s wall "
        f"(compile_s={clock.total - c0:.3f} summed over threads)")

    refs = {"reduce": state_r, "rnp": state_s}
    for (algo, ex), (exe, arrays, compile_s) in compiled.items():
        t1 = time.perf_counter()
        out = jax.block_until_ready(exe(arrays))
        wall_s = time.perf_counter() - t1
        if algo == "reduce":
            w, status, *_, offset, iters = out
        else:
            w, status, members, offset, _, iters = out
        ref, pg = refs[algo], builds[algo][1]
        tag = f"mesh/{algo}/{ex}"
        check(np.array_equal(np.asarray(status),
                             np.asarray(ref.status).reshape(p, pg.V))
              and np.array_equal(np.asarray(w),
                                 np.asarray(ref.w).reshape(p, pg.V))
              and int(np.asarray(offset).sum()) == int(ref.offset),
              f"{tag}: status/w/offset differ from the union path")
        msg = f"{tag}: status, w, offset"
        if algo == "rnp":
            glob = _mesh_members(pg, members)
            check(g_s.is_independent_set(glob),
                  f"{tag}: not an independent set")
            weight = int(g_s.set_weight(glob))
            check(weight == weight_s,
                  f"{tag}: weight {weight} != union {weight_s}")
            msg += f", weight == union weight={weight} peels"
        else:
            msg += " == union rounds"
        log(f"{msg}={int(np.asarray(iters)[0])} compile_s={compile_s:.3f} "
            f"wall_s={wall_s:.3f}")
        del out, arrays


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the one-PE-per-chip mesh phase")
    ap.add_argument("--reduce-log2n", type=int, default=REDUCE_LOG2N,
                    help="log2 n of the one-chip reduce (20: the paper's "
                         "per-core size)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: FAILED: no src/repro next to {__file__}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        device = device_gate(args.chips)
        log(f"device: {device}")
        from repro.launch.cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        clock = CompileClock()
        t0 = time.perf_counter()
        if args.chips == 4:
            phase_mesh(clock, MESH_REDUCE_LOG2N, MESH_SOLVE_LOG2N, SEED)
        else:
            phase_reduce(clock, args.reduce_log2n, SEED)
            phase_solve(clock, SOLVE_LOG2N, SEED)
            phase_serve(clock, SERVE_BATCHES, SEED)
        log(f"total_s={time.perf_counter() - t0:.3f} "
            f"compile_s={clock.total:.3f}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
