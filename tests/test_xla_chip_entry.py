"""CPU-side pins for the chip entry point and what it rests on:
``chip_smoke.py`` refuses to run without a TPU and labels its rehearsal,
the compile cache stays at one path, ``on_tpu()`` does not hide a broken
backend, and a refused AOT compile / native build is said out loud."""

import contextlib
import json
import logging
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run_smoke(*argv, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # this host has no TPU either way
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py"),
                           *argv], env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO)


def test_chip_smoke_without_tpu_fails_fast_and_prints_no_result():
    p = _run_smoke(timeout=60)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    # no phase line, no result line, nothing under a device name
    assert p.stdout.strip() == ""


def test_chip_smoke_rehearsal_is_an_explicit_labelled_argument():
    p = _run_smoke("--rehearsal", "--phases", "device")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines, p.stdout
    for doc in lines:                   # every line says what it is
        assert doc["rehearsal"] is True and doc["device"] == "cpu"
    final = lines[-1]
    assert final["ok"] is True
    assert final["partial"] == ["device"]   # a subset is not the contract
    phase = lines[0]
    assert phase["phase"] == "device" and phase["platform"] == "cpu"
    assert phase["compile_cache_dir"] == os.path.join(REPO, ".jax_cache")


def test_chip_smoke_rejects_unknown_phase():
    import chip_smoke
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(["--phases", "device,warp_drive"])
    assert e.value.code == 2


def test_sum_order_rtol_is_set_from_the_dtype():
    import chip_smoke
    eps = 2.0 ** -23
    assert chip_smoke.sum_order_rtol(1 << 20, 1) == pytest.approx(20 * eps)
    assert chip_smoke.sum_order_rtol(1 << 20, 2) == pytest.approx(40 * eps)
    assert chip_smoke.sum_order_rtol(1, 1) == pytest.approx(eps)


# --- compile cache ---------------------------------------------------------

@pytest.fixture
def _cache_config():
    """Snapshot/restore the three jax.config values the function touches
    (the suite itself runs with the persistent cache disabled)."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)


def test_compile_cache_default_is_checkout_relative_from_any_cwd(
        _cache_config, tmp_path, monkeypatch):
    from byteps_tpu.utils import compile_cache as cc
    seen = []
    for sub in ("a", "b"):
        (tmp_path / sub).mkdir()
        monkeypatch.chdir(tmp_path / sub)
        jax.config.update("jax_compilation_cache_dir", None)
        seen.append(cc.enable_compile_cache())
    want = os.path.join(REPO, ".jax_cache")
    assert seen == [want, want]
    assert jax.config.jax_compilation_cache_dir == want
    # nothing run-specific in the path: no temp dir, pid or timestamp
    assert str(os.getpid()) not in want and "tmp" not in want.lower()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_leaves_an_already_set_directory_alone(_cache_config):
    from byteps_tpu.utils import compile_cache as cc
    # JAX reads JAX_COMPILATION_CACHE_DIR into this config value at import
    jax.config.update("jax_compilation_cache_dir", "/set/by/the/environment")
    assert cc.enable_compile_cache() == "/set/by/the/environment"
    assert jax.config.jax_compilation_cache_dir == "/set/by/the/environment"


def test_compile_cache_env_var_wins_and_aot_shares_the_cache(tmp_path):
    """In a fresh process: the env var's directory is used untouched, a
    jit compile fills it, and the engine-style ``.lower().compile()`` of
    the same program then HITS it (both go through one cache)."""
    code = r"""
import json, os, jax, jax.numpy as jnp
from jax import monitoring
from byteps_tpu.utils.compile_cache import enable_compile_cache
events = []
monitoring.register_event_listener(lambda name, **kw: events.append(name))
d = enable_compile_cache()
f = jax.jit(lambda x: jnp.tanh(x) @ x.T + 3)
x = jnp.ones((64, 64))
f(x).block_until_ready()
files = sorted(os.listdir(d))
jax.clear_caches()
hits0 = events.count('/jax/compilation_cache/cache_hits')
jax.jit(lambda x: jnp.tanh(x) @ x.T + 3).lower(x).compile()
print(json.dumps({"dir": d, "files": len(files),
                  "aot_hits": events.count(
                      '/jax/compilation_cache/cache_hits') - hits0}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["dir"] == str(tmp_path / "cache")
    assert doc["files"] >= 1            # sub-second programs are cached too
    assert doc["aot_hits"] >= 1
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))


# --- no fallback that hides the device -------------------------------------

@contextlib.contextmanager
def _package_log(caplog):
    """Capture the package logger's warnings (it does not propagate to
    the root logger, so caplog's handler is attached directly)."""
    from byteps_tpu.common.logging import get_logger
    logger = get_logger()
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.WARNING, logger=logger.name):
            yield
    finally:
        logger.removeHandler(caplog.handler)


def test_on_tpu_propagates_a_backend_error(monkeypatch):
    from byteps_tpu.ops import pallas_kernels as pk

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    assert pk.on_tpu() is False         # this suite's backend is the CPU
    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        pk.on_tpu()


def test_refused_aot_compile_is_counted_and_logged_with_its_message(caplog):
    from byteps_tpu.comm import collectives
    from byteps_tpu.comm.mesh import CommContext, _build_mesh
    from byteps_tpu.common.telemetry import counters

    comm = CommContext(mesh=_build_mesh(jax.devices()[:1], 1), n_dcn=1,
                       n_ici=1)

    class Refused:
        def lower(self, *a):
            raise RuntimeError("Mosaic failed to compile TPU kernel: "
                               "scoped vmem limit exceeded")

        def __call__(self, *a):
            return "lazy"

    comm.jit_cache["k"] = Refused()
    with _package_log(caplog):
        assert collectives.aot_compile(comm, "k", []) is False
    assert counters.get("engine.aot_compile_failed") == 1
    assert "scoped vmem limit exceeded" in caplog.text
    assert comm.jit_cache["k"]() == "lazy"      # lazy path left in place


def test_native_build_failure_names_its_cause():
    from byteps_tpu import native
    err = subprocess.CalledProcessError(
        1, ["g++"], stderr="core.cc:12:1: error: expected ';'\n")
    assert "expected ';'" in native._describe_failure(err)
    assert "g++ exited 1" in native._describe_failure(err)
    missing = FileNotFoundError(2, "No such file or directory", "g++")
    assert "g++ not found" in native._describe_failure(missing)


def test_snapshot_names_the_engines_one_queue():
    """Chunk tasks go through common/scheduler.py ChunkScheduler whatever
    BYTEPS_NATIVE says (it gates the host reducer / CRC / Elias coder
    only), and ``metrics_snapshot()["scheduler"]`` says so."""
    import numpy as np

    import byteps_tpu as bps
    from byteps_tpu.common.config import Config

    for use_native in (True, False):
        bps.init(Config(use_native=use_native))
        try:
            assert bps.metrics_snapshot(light=True)["scheduler"] \
                == "ChunkScheduler"
            x = np.random.randn(bps.size(), 1024).astype(np.float32)
            out = bps.push_pull(x, "one_queue")
            np.testing.assert_allclose(np.asarray(out), x.mean(0),
                                       rtol=1e-5, atol=1e-6)
        finally:
            bps.shutdown()


def test_engine_mode_step_never_loads_the_native_library(monkeypatch):
    """bps.init -> DistributedOptimizer.update under the default Config
    builds and opens no .so: g++ is not on the training path."""
    import numpy as np
    import optax

    import byteps_tpu as bps
    from byteps_tpu import native
    from byteps_tpu.common.config import Config
    from byteps_tpu.jax import DistributedOptimizer

    # as in a fresh process (another test may have loaded the library)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    bps.init(Config())
    try:
        params = {"w": np.zeros((64, 32), np.float32),
                  "b": np.zeros((32,), np.float32)}
        opt = DistributedOptimizer(optax.sgd(0.1))
        state = opt.init(params)
        grads = {k: np.ones((bps.size(),) + v.shape, np.float32)
                 for k, v in params.items()}
        upd, state = opt.update(grads, state, params)
        np.testing.assert_allclose(np.asarray(upd["w"]), -0.1, rtol=1e-6)
    finally:
        bps.shutdown()
    assert native._lib is None and not native._load_failed
