"""The rule tests' static factors: built once per graph, never in a sweep.

Every Aux builder must fill the static mask fields with exactly the
expressions the rules would otherwise evaluate on the fly, and the traced
sweep must gather nothing that is a function of the Aux alone.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import distributed as D
from repro.core import engine as E
from repro.core import partition as part
from repro.core import rules as R
from repro.core import serve as SV
from repro.core.local_reduce import make_aux
from repro.graphs import generators as gen


def _expected(row, col, gid, is_local, win_complete, window, edge_common):
    """The static factors as the rules wrote them inline."""
    ec = edge_common
    min_gid = np.minimum(gid[row], gid[col])
    return dict(
        e_local=is_local[row] & is_local[col],
        e_up=gid[row] > gid[col],
        ec_ok=(is_local[ec] & (gid[ec] < min_gid[:, None]) & (gid[ec] >= 0)),
        gid_col=gid[col],
        wc_col=win_complete[col],
        win_real=gid[window] >= 0,
    )


def _assert_aux_masks(aux):
    a = {k: np.asarray(getattr(aux, k)) for k in R.Aux._fields}
    want = _expected(a["row"], a["col"], a["gid"], a["is_local"],
                     a["win_complete"], a["window"], a["edge_common"])
    assert set(want) == set(R.STATIC_MASKS)
    for k, v in want.items():
        assert a[k].dtype == v.dtype, k
        np.testing.assert_array_equal(a[k], v, err_msg=k)


def _pg(p, n=300, seed=3):
    g = gen.rgg2d(n, avg_deg=7, seed=seed)
    return g, part.partition_graph(g, p, window_cap=8, common_cap=4)


def test_make_aux_fills_static_masks():
    _, pg = _pg(1)
    _assert_aux_masks(make_aux(pg, pe=0))


@pytest.mark.parametrize("p", [1, 4])
def test_pack_union_problem_fills_static_masks(p):
    _, pg = _pg(p)
    host = D.pack_union_problem(pg)
    _assert_aux_masks(host.aux)
    # the union masks are the per-PE masks, PE after PE
    for k in R.STATIC_MASKS:
        np.testing.assert_array_equal(
            getattr(host.aux, k),
            getattr(pg, k).reshape((-1,) + getattr(pg, k).shape[2:]))


def test_service_host_pack_fills_static_masks():
    g = gen.gnm(60, 150, seed=4)
    cell = SV.bucket_for(g.n, 2 * g.m)
    topo = SV._pack_topology(g, cell, "blocked")
    _assert_aux_masks(topo.prob.aux)


def test_compact_partition_rung_fills_static_masks():
    _, pg = _pg(4, n=400, seed=5)
    state, _, _ = D.disredu(pg, D.DisReduConfig(heavy_k=6, mode="sync"))
    status, w = np.asarray(state.status), np.asarray(state.w)
    assert D.ghosts_consistent(pg, status)
    small = part.compact_partition(pg, status, w)
    assert small.V < pg.V
    _assert_aux_masks(D.pack_union_problem(small).aux)
    for pe in range(small.p):
        _assert_aux_masks(make_aux(small, pe=pe))


UNPACK_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed as D, partition as part, rules as R
    from repro.graphs import generators as gen
    from repro.launch.mesh import make_host_mesh

    g = gen.rgg2d(300, avg_deg=7, seed=3)
    pg = part.partition_graph(g, 4, window_cap=8, common_cap=4)
    arrs = pg.device_arrays()
    keys = list(arrs)

    def per_pe(*args):
        aux, _, _, _ = D._unpack_per_pe(pg, keys, args)
        return tuple(getattr(aux, k)[None] for k in R.STATIC_MASKS)

    mesh = make_host_mesh(4)
    fn = jax.shard_map(per_pe, mesh=mesh,
                       in_specs=tuple(P("pe") for _ in keys),
                       out_specs=(P("pe"),) * len(R.STATIC_MASKS),
                       check_vma=False)
    out = jax.jit(fn)(*(jnp.asarray(arrs[k]) for k in keys))
    np.savez(sys.argv[1], **{k: np.asarray(v)
                             for k, v in zip(R.STATIC_MASKS, out)})
    print("RESULT " + str(len(jax.devices())))
""")


def test_unpack_per_pe_builds_static_masks_on_four_devices(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    path = str(tmp_path / "masks.npz")
    r = subprocess.run([sys.executable, "-c", UNPACK_SCRIPT, path],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RESULT 4" in r.stdout
    got = np.load(path)
    _, pg = _pg(4)
    for pe in range(pg.p):
        want = _expected(pg.row[pe], pg.col[pe], pg.gid[pe], pg.is_local[pe],
                         pg.win_complete[pe], pg.window[pe],
                         pg.edge_common[pe])
        for k, v in want.items():
            assert got[k].dtype == v.dtype, k
            np.testing.assert_array_equal(got[k][pe], v, err_msg=(pe, k))


# --------------------------------------------------------------------- #
# structural guard: the sweep gathers state only
# --------------------------------------------------------------------- #
def _gathers(jaxpr, static_in):
    """[(out shape, name stack, all inputs static)] of every gather.

    An input is static when it is computed from the Aux (and constants)
    alone; nested jit bodies inherit their operands' flags.
    """
    static = dict(zip(jaxpr.invars, static_in))
    static.update((v, True) for v in jaxpr.constvars)
    is_static = lambda v: isinstance(v, jcore.Literal) or static[v]
    found = []
    for eqn in jaxpr.eqns:
        flags = [is_static(v) for v in eqn.invars]
        if eqn.primitive.name == "gather":
            found.append((tuple(eqn.outvars[0].aval.shape),
                          str(eqn.source_info.name_stack), all(flags)))
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if not isinstance(sub, jcore.Jaxpr):
                    continue
                if len(sub.invars) == len(flags):
                    found += _gathers(sub, flags)
                else:  # a combiner (scatter's update_jaxpr) gathers nothing
                    assert not _gathers(sub, [False] * len(sub.invars))
        for v in eqn.outvars:
            static[v] = all(flags)
    return found


@pytest.mark.parametrize("backend", ["jnp", "blocked"])
def test_sweep_gathers_no_aux_only_operand(backend):
    _, pg = _pg(1, n=120, seed=1)
    prob = D.build_union_problem(pg, backend, 8)
    aux = prob.aux
    state = R.init_state(prob.w0, prob.is_local, prob.is_ghost)
    V, Dw = aux.window.shape
    En, Dc = aux.edge_common.shape
    assert len({V, En}) == 2 and len({Dw, Dc}) == 2

    def one(state, aux, plan):
        state = E.sweep(state, aux, schedule="cheap-fused", backend=backend,
                        plan=plan)
        return R.rule_heavy_vertex(state, aux, 6)

    closed = jax.make_jaxpr(one)(state, aux, prob.plan)
    flags = ([False] * len(jax.tree.leaves(state))
             + [True] * len(jax.tree.leaves(aux))
             + [False] * len(jax.tree.leaves(prob.plan)))
    found = _gathers(closed.jaxpr, flags)
    assert found
    static = [(shape, scope) for shape, scope, st in found if st]
    assert not static, f"gathers of Aux-only data in the sweep: {static}"
    wide = sorted(scope for shape, scope, _ in found if shape == (En, Dc))
    assert wide == ["mwis.rule.basic_single_edge",
                    "mwis.rule.extended_single_edge"]


def test_static_masks_numpy_and_jax_agree():
    _, pg = _pg(1)
    args = (pg.row[0], pg.col[0], pg.gid[0], pg.is_local[0],
            pg.win_complete[0], pg.window[0], pg.edge_common[0])
    host = R.static_masks(*args)
    dev = jax.jit(R.static_masks)(*map(jnp.asarray, args))
    for k in R.STATIC_MASKS:
        assert isinstance(host[k], np.ndarray), k
        np.testing.assert_array_equal(host[k], np.asarray(dev[k]), err_msg=k)
