"""Explicit JAX platform selection (the launch-path analogue of choosing an
MPI hostfile) and the one place the compile cache is configured.

The reference picks its "platform" implicitly: whatever hosts ``mpiexec -n N``
was given (reference README.md:12).  A JAX process instead binds to a PJRT
backend the first time any backend-touching API runs, and an accelerator
belongs to ONE process at a time — so the choice is made once, in the
process that will do the work, never by a helper child:

* ``cpu``  — pin the host backend (optionally with N virtual devices for
  SPMD testing, SURVEY.md §4).
* ``tpu``  — initialise the backend here and fail if it is not a TPU.
* ``auto`` — pin nothing; JAX and ``JAX_PLATFORMS`` decide.

:func:`select` is what the CLI, the benchmark and the fleet worker call;
nothing re-pins a process whose backend is already up, and no path falls
back from a requested platform to another one.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

PLATFORMS = ("auto", "cpu", "tpu")

# the checkout root: <root>/<package>/utils/platform.py
REPO_ROOT = Path(__file__).resolve().parents[2]
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class PlatformUnavailable(RuntimeError):
    """The requested platform is not the one JAX initialised."""


def force_host_device_count(n: Optional[int], env=None) -> None:
    """Request ``n`` virtual CPU devices (must run before backend init).

    This is the launcher's replacement for ``mpiexec -n N`` when no
    accelerator is present: SPMD code sees N devices on one host.  Any
    pre-existing count in ``XLA_FLAGS`` is *replaced* — an explicit
    ``--num_devices`` must win over a stale exported flag; ``n=None``
    strips a stale count without setting a new one.  ``env`` defaults to
    ``os.environ`` (pass a dict to prepare a subprocess environment).
    """
    if env is None:
        env = os.environ
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    if n is not None:
        flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)


def pin(platform: str = "auto", num_devices: Optional[int] = None) -> None:
    """Pin this process's JAX platform.  Must run before backend init.

    Only ``cpu`` pins anything (the env var for children, the config for
    this process); ``tpu`` and ``auto`` leave the choice to JAX.
    """
    if platform not in PLATFORMS:
        raise ValueError(f"platform must be one of {PLATFORMS}, got {platform!r}")
    if num_devices is not None:
        force_host_device_count(num_devices)
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")


def select(platform: str = "auto", num_devices: Optional[int] = None,
           log=None) -> Dict[str, object]:
    """Pin (``cpu`` only), initialise the backend, name it, and hold an
    explicit request to what came up: ``cpu``/``tpu`` raise
    :class:`PlatformUnavailable` when the live backend is another platform
    (a missing chip, or a process whose backend was already initialised
    elsewhere).  Returns ``{"platform", "device_kind", "n_devices"}``."""
    pin(platform, num_devices)
    import jax

    devs = jax.devices()      # the backend comes up here, in this process
    info = {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "n_devices": len(devs)}
    if log is not None:
        log(f"platform: {info['platform']} | device_kind: "
            f"{info['device_kind']} | devices: {info['n_devices']}")
    if platform != "auto" and info["platform"] != platform:
        raise PlatformUnavailable(
            f"platform {platform!r} was asked for, but JAX initialised "
            f"{info['n_devices']}x {info['device_kind']} "
            f"(platform {info['platform']!r}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    return info


def compile_cache() -> str:
    """Place JAX's persistent compile cache; call before the first jit.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
    set in code.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it
    never comes from a temp dir, a pid or the time.  Returns the directory
    in use.

    The ``jax.named_scope`` path of every operation is made part of a
    program's cache key.  By default JAX leaves all metadata out of the key,
    so a program whose scopes changed would load the executable cached
    before the change, and a device trace of it would carry the old names
    (seen on the chip: a cache written before the scopes existed gave
    ``jit(shard_step)/jvp()/dot_general``).  The device trace is read by
    those names (``benchmark/reducers/scopes.py``), so they belong to the
    program's identity.  Source files and lines are kept out of the
    locations instead: with them in the key, a moved checkout or a shifted
    line would compile everything again.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env_dir = os.environ.get(COMPILE_CACHE_ENV)
    if env_dir:
        return env_dir
    cache_dir = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
