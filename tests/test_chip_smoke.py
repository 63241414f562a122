"""``chip_smoke.py``'s phases, rehearsed on the CPU at a small size.

The script itself refuses to run without a TPU; its phase functions are
what it runs there, so they are checked here end to end: the two
one-chip sessions against the host engines, and the lane-sharding and
partition paths against single placement on whatever devices exist.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", _ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_single_chip_phases_match_host_engine(smoke):
    # capacity 16 grows mid-stream: the grow-and-replay path runs too
    rows = smoke.single_chip_phases(seed=0, n_jobs=200, capacity=16)
    assert [r["session"] for r in rows] == ["pe_w_jnp", "ff_kernel",
                                            "tenancy"]
    assert [r["search_path"] for r in rows[:2]] == ["jnp", "kernel"]
    for r in rows:
        assert r["matched"] == r["jobs"] == 200, r
    for r in rows[:2]:
        assert r["growths"] > 0 and r["cancelled"] > 0, r


def test_mesh_phases_match_single_placement(smoke):
    n_dev = len(jax.devices())
    lanes = smoke.lane_sharding_phase(0, n_jobs=40, n_devices=n_dev)
    assert lanes["lanes"] == 28 and lanes["mismatches"] == 0, lanes
    parts = smoke.partition_phase(0, n_jobs=60, n_devices=n_dev)
    assert parts["mismatches"] == 0 and parts["jobs"] == 60, parts


def test_mismatches_ignore_start_of_rejections(smoke):
    acc = np.array([True, False, True])
    ref = np.array([True, False, True])
    assert smoke.mismatches(acc, [5, -1, 7], ref, [5, 99, 7]) == 0
    assert smoke.mismatches(acc, [5, -1, 8], ref, [5, -1, 7]) == 1
    assert smoke.mismatches(~acc, [5, -1, 7], ref, [5, -1, 7]) == 3


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("from_env", [False, True],
                         ids=["in-checkout", "from-env"])
def test_compile_cache_placement(from_env, tmp_path, monkeypatch):
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import use_compile_cache
    default = tmp_path / ".jax_cache"
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    compilation_cache.reset_cache()
    try:
        got = use_compile_cache(default)
        if from_env:
            # JAX reads the variable itself; the helper sets no dir
            assert got == str(tmp_path / "env")
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]
        else:
            assert got == str(default)
            assert jax.config.jax_compilation_cache_dir == str(default)
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
            assert any(default.iterdir())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
