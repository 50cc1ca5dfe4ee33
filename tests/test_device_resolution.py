"""Nothing hides the device: backend resolution, the boot report, the
compile cache's place, and the server that must not start without its
chip (ISSUE 21)."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from minio_tpu.codec import backend as backend_mod
from minio_tpu.codec.backend import CpuBackend, TpuBackend
from minio_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _concrete(be):
    while hasattr(be, "inner"):
        be = be.inner
    return be


@pytest.fixture(autouse=True)
def _fresh():
    backend_mod.reset_backend()
    yield
    backend_mod.reset_backend()


@pytest.mark.parametrize("name", ["auto", "tpu"])
def test_devices_raising_fails_resolution(monkeypatch, name):
    """A chip that is missing or held by another process makes
    jax.devices() raise; that must surface, never become the CPU codec."""

    def busy():
        raise RuntimeError("Unable to initialize backend 'tpu': busy")

    monkeypatch.setattr(jax, "devices", busy)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        backend_mod.get_backend(name)
    monkeypatch.setenv("MINIO_ERASURE_BACKEND", name)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        backend_mod.backend_info()


def test_auto_without_accelerator_says_cpu_codec():
    be = _concrete(backend_mod.get_backend("auto"))
    assert isinstance(be, CpuBackend)


def test_tpu_by_name_needs_a_tpu_or_a_pinned_platform(monkeypatch):
    # the suite pins JAX to cpu (conftest), so the name is honoured...
    assert isinstance(_concrete(backend_mod.get_backend("tpu")), TpuBackend)
    # ...but on a platform JAX merely fell back to it is an error
    monkeypatch.setattr(backend_mod, "_pinned_to", lambda platform: False)
    with pytest.raises(RuntimeError, match="JAX found platform 'cpu'"):
        backend_mod.get_backend("tpu")
    with pytest.raises(ValueError):
        backend_mod.get_backend("gpu")


def test_backend_info_names_what_jax_reports(monkeypatch):
    monkeypatch.setenv("MINIO_ERASURE_BACKEND", "tpu")
    info = backend_mod.backend_info()
    dev = jax.devices()
    assert info["backend"] == "tpu"
    assert info["platform"] == dev[0].platform == "cpu"
    assert info["device_kind"] == dev[0].device_kind
    assert info["device_count"] == len(dev) == len(info["devices"])
    assert info["jax"] == jax.__version__
    # the resolved backend and what JAX reports: nothing here chooses a
    # kernel, so no key names one
    assert set(info) == {
        "backend", "batched", "platform", "device_kind", "device_count",
        "devices", "jax", "jaxlib", "libtpu", "compile_cache", "placement",
    }
    assert "over 8 devices" in info["placement"]
    monkeypatch.setenv("MINIO_MESH", "0")
    assert "pinned to device 0" in backend_mod.backend_info()["placement"]
    backend_mod.reset_backend()
    monkeypatch.setenv("MINIO_ERASURE_BACKEND", "cpu")
    info = backend_mod.backend_info()
    assert info["backend"] == "cpu" and info["codec"] in ("native", "numpy")
    assert "platform" not in info


def test_routed_single_device_pass_lands_on_the_routed_device():
    """A batch the router places on chip 3 must run on chip 3, not on
    the process default device."""
    import numpy as np

    from minio_tpu.parallel import rules as prules

    be = TpuBackend()
    target = jax.devices()[3]
    with prules.placed((target,)):
        staged = be._to_device(np.zeros((1, 2, 64), np.uint32))
    assert staged.devices() == {target}
    assert be._to_device(np.zeros(4, np.uint32)).devices() == {
        jax.devices()[0]
    }


_CACHE_VARS = (
    "JAX_COMPILATION_CACHE_DIR",
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
)


@pytest.fixture
def _restore_cache_config():
    """setup_compile_cache() writes the process environment and JAX's
    config; put both back so the rest of the suite stays uncached."""
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    saved_env = {k: os.environ.get(k) for k in _CACHE_VARS}
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", saved[1]
    )
    for k, v in saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_compile_cache_honours_the_variable(tmp_path, _restore_cache_config):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "x")
    os.environ.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    assert jaxenv.setup_compile_cache() == str(tmp_path / "x")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "x")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jaxenv.compile_cache_stats()["dir"] == str(tmp_path / "x")


def test_compile_cache_defaults_into_the_checkout(_restore_cache_config):
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "2.5"
    want = os.path.join(REPO, ".jax_cache")
    assert jaxenv.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # an operator's threshold stands
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 2.5
    # and the default is exported, so children land in the same place
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_server_does_not_start_when_devices_raise(tmp_path):
    """JAX_PLATFORMS=tpu on a host without one: jax.devices() raises, and
    the server must exit non-zero with one line instead of serving from
    the host codec."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="tpu",
        PYTHONPATH=REPO,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
    )
    env.pop("MINIO_ERASURE_BACKEND", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "minio_tpu.server",
            "--address", "127.0.0.1:0",
            *[str(tmp_path / f"d{i}") for i in range(4)],
        ],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "codec backend unavailable, not starting" in proc.stderr
    assert "listening" not in proc.stdout


def test_chip_smoke_refuses_without_an_accelerator(tmp_path):
    """The driver's plain invocation, here where JAX is held to the CPU:
    non-zero, one line saying why, and no result line."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not a TPU" in proc.stderr
    env["MINIO_TPU_CODEC_INTERPRET"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "MINIO_TPU_CODEC_INTERPRET" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the rest of the repo next to it: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no minio_tpu package" in proc.stderr


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver parses the last stdout line and refuses any other key."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    env = {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
        "jax": "0.9.0", "cache_dir": "/x",
    }
    line = smoke.result_line(env)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
