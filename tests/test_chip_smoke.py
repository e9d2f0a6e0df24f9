"""chip_smoke.py off the chip: it refuses the CPU, and its phases' checks
hold at tiny sizes in interpret mode; the compile-cache helper."""

import os
import re
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("phase", ["reduce", "solve"])
def test_chip_smoke_phase_checks_hold_on_cpu(smoke, phase, capsys):
    clock = smoke.CompileClock()
    if phase == "reduce":
        smoke.phase_reduce(clock, 9, 0)
    else:
        smoke.phase_solve(clock, 8, 0)
    assert f"{phase}: pallas == jnp" in capsys.readouterr().out


def test_chip_smoke_mesh_phase_holds_on_four_cpu_devices():
    # the --chips 4 phase needs four devices: a child process with forced
    # CPU host devices (on the chip it runs in one process)
    code = ("import chip_smoke as C; "
            "C.phase_mesh(C.CompileClock(), 7, 5, 0); print('MESH OK')")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    for exchange in ("allgather", "a2a"):
        assert f"mesh/reduce/{exchange}: status, w, offset == union" \
            in r.stdout
        assert f"mesh/rnp/{exchange}: status, w, offset, weight == union" \
            in r.stdout
    assert "MESH OK" in r.stdout


def test_smoke_check_raises_on_a_broken_contract(smoke):
    with pytest.raises(smoke.SmokeFailure, match="differ"):
        smoke.check(False, "pallas and jnp differ")


@pytest.fixture
def cache_key_settings():
    """Restore the cache-key settings that enable_compile_cache sets."""
    keys = ("jax_compilation_cache_include_metadata_in_key",
            "jax_hlo_source_file_canonicalization_regex")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_keys_on_op_metadata(monkeypatch, tmp_path,
                                           cache_key_settings):
    # the programs' mwis.* scopes live only in op metadata: a cache key
    # without it would hand back a program compiled with other names
    from repro.launch import cache

    monkeypatch.setenv(cache.ENV, str(tmp_path))
    cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.sub(regex, "", "/any/checkout/src/repro/core/engine.py") == (
        "src/repro/core/engine.py")


def test_compile_cache_leaves_the_env_dir_to_jax(monkeypatch, tmp_path,
                                                 cache_key_settings):
    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_key_settings):
    from repro.launch import cache

    monkeypatch.delenv(cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
