"""shard_map production path == union simulation path (subprocess with
multiple host devices; exercises lax collectives incl. the a2a exchange)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import numpy as np
    import jax
    from repro.core import distributed as D, partition as part, solvers as S
    from repro.graphs import generators as gen
    from repro.launch.mesh import make_host_mesh

    g = gen.rgg2d(400, avg_deg=7, seed=5)
    pg = part.partition_graph(g, 4, window_cap=8)
    out = {}
    for exchange in ("allgather", "a2a"):
        cfg = D.DisReduConfig(heavy_k=6, mode="sync", exchange=exchange)
        mesh = make_host_mesh(4)
        run, keys = S.solver_shard_map_fn(pg, cfg, mesh, "rnp", axis="pe")
        import jax.numpy as jnp
        arrays = {k: jnp.asarray(v) for k, v in pg.device_arrays().items()}
        w, status, members, offset, logn, peels = run(arrays)
        members = np.asarray(members)
        gids = pg.gid
        glob = np.zeros(g.n, dtype=bool)
        for i in range(4):
            sel = members[i] & pg.is_local[i]
            glob[gids[i][sel]] = True
        assert g.is_independent_set(glob), exchange
        out[exchange] = int(g.weights[glob].sum())
    # union-path result for comparison
    members_u, _, _ = S.solve(pg, "rnp", D.DisReduConfig(heavy_k=6, mode="sync"))
    out["union"] = int(g.weights[members_u].sum())
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_shard_map_matches_union_and_a2a_matches_allgather(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    )
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=1800,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    # all three execution paths produce identical solution weights
    assert out["allgather"] == out["a2a"] == out["union"], out


PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import distributed as D, partition as part
    from repro.graphs import generators as gen
    from repro.launch.mesh import make_host_mesh

    g = gen.rgg2d(400, avg_deg=7, seed=5)
    pg = part.partition_graph(g, 4, window_cap=8)
    V = pg.V
    for schedule, backend in (("cheap", "jnp"), ("cheap-fused", "blocked")):
        for mode in ("sync", "async"):
            cfg = D.DisReduConfig(heavy_k=6, mode=mode, schedule=schedule,
                                  backend=backend)
            mesh = make_host_mesh(4)
            run, keys = D.disredu_shard_map_fn(pg, cfg, mesh, axis="pe")
            w, status, _, _, _, _, offset, _ = run()
            su, _, _ = D.disredu(pg, cfg)   # union path, same config
            tag = f"{schedule}/{backend}/{mode}"
            assert np.array_equal(
                np.asarray(status), np.asarray(su.status).reshape(4, V)
            ), f"status diverged: {tag}"
            assert np.array_equal(
                np.asarray(w), np.asarray(su.w).reshape(4, V)
            ), f"weights diverged: {tag}"
            assert int(np.asarray(offset).sum()) == int(su.offset), \\
                f"offset diverged: {tag}"
    print("PARITY OK")
""")


@pytest.mark.slow
def test_shard_map_reduction_bit_identical_to_union():
    """Engine path parity across execution paths: DisRedu{S,A} under
    shard_map produces bit-identical per-PE status/w (and total offset) to
    the union simulation, for both refresh granularities and backends."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    )
    r = subprocess.run(
        [sys.executable, "-c", PARITY_SCRIPT], capture_output=True,
        text=True, env=env, timeout=1800,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PARITY OK" in r.stdout
