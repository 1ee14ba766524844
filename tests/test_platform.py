"""The launch contract (utils/platform.py): one process per device, no
probe child, no fallback from a requested platform, and one helper that
places the compile cache."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from neural_networks_parallel_training_with_mpi_tpu import cli
from neural_networks_parallel_training_with_mpi_tpu.train import telemetry
from neural_networks_parallel_training_with_mpi_tpu.utils import (
    platform as plat,
)

REPO = Path(__file__).resolve().parent.parent


def test_select_cpu_names_the_backend():
    lines = []
    info = plat.select("cpu", log=lines.append)
    assert info["platform"] == "cpu" and info["n_devices"] == len(
        jax.devices())
    assert lines == [f"platform: cpu | device_kind: {info['device_kind']} "
                     f"| devices: {info['n_devices']}"]


def test_select_tpu_raises_when_the_backend_is_not_a_tpu():
    with pytest.raises(plat.PlatformUnavailable, match="platform 'cpu'"):
        plat.select("tpu")


def test_pin_rejects_an_unknown_platform():
    with pytest.raises(ValueError, match="platform must be one of"):
        plat.pin("gpu")


def test_second_cli_main_spawns_nothing_and_does_not_repin(monkeypatch):
    """A process whose backend is up runs ``cli.main`` again without a
    helper child and without touching the platform pin (the old probe
    child could not get a chip its parent held, and ``auto`` then re-pinned
    the live process to the CPU)."""
    real_popen, children = subprocess.Popen, []

    def popen(cmd, *a, **k):
        children.append(cmd if isinstance(cmd, str) else list(cmd))
        return real_popen(cmd, *a, **k)

    monkeypatch.setattr(subprocess, "Popen", popen)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or "unused")
    before = (jax.config.jax_platforms, os.environ.get("JAX_PLATFORMS"),
              os.environ.get("XLA_FLAGS"), jax.devices())
    for _ in range(2):
        assert cli.main(["--platform", "auto", "--nepochs", "1"]) == 0
    # (a library import may shell out to a system tool such as lscpu;
    # what must never start is another Python that could want the device)
    assert not [c for c in children if "python" in str(c[0] if
                isinstance(c, list) else c)], children
    assert (jax.config.jax_platforms, os.environ.get("JAX_PLATFORMS"),
            os.environ.get("XLA_FLAGS"), jax.devices()) == before


def test_compile_cache_honours_the_environment(monkeypatch):
    monkeypatch.setenv(plat.COMPILE_CACHE_ENV, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert plat.compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(plat.COMPILE_CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert plat.compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
        assert plat.compile_cache() == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_mfu_exists_on_a_known_tpu_only():
    assert telemetry.telemetry_peak_flops("TPU v5 lite", "tpu") == 197e12
    assert telemetry.telemetry_peak_flops("cpu", "cpu") is None
    with pytest.raises(ValueError, match="no peak FLOPs/s entry"):
        telemetry.peak_flops_per_chip("TPU v9x")
