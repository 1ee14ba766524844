"""What every kind of cell shares: finding a cell's files by name, the device
check, the profiler window, and the lines a run prints."""

from __future__ import annotations

import functools
import importlib.util
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
OUT = BENCH / "out"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load(kind: str, name: str, bench: Path) -> dict:
    return json.loads((bench / kind / f"{name}.json").read_text())


# model keys the harness itself reads, whatever the family
HARNESS_KEYS = ("vocab_size", "n_layers", "param_dtype")


@functools.lru_cache(maxsize=None)
def load_family(name: str, bench: Path = BENCH):
    """The module ``families/<name>.py`` under ``bench``: what the benchmark
    knows of one kind of block."""
    path = bench / "families" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown family {name!r}: there is no "
                         f"families/{name}.py under {bench}")
    importlib.import_module("benchmark.families")   # its relative imports
    spec = importlib.util.spec_from_file_location(
        f"benchmark.families.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def model_of(config: dict, bench: Path = BENCH) -> dict:
    """The flat model dict a configuration's ``mapping`` spells: a value
    ``"$key"`` is read from the published keys, anything else is itself.
    ``family`` (the module the configuration names) and ``config`` (its
    name) ride along, so that whoever holds the dict can ask the family."""
    if "family" not in config:
        raise ValueError(f"configuration {config['name']!r} names no family")
    family = load_family(config["family"], bench)
    mapping, pub = config["mapping"], config["published"]
    for key in (*HARNESS_KEYS, *family.MODEL_KEYS):
        if key not in mapping:
            raise ValueError(
                f"configuration {config['name']!r}: its mapping lacks "
                f"{key!r}, which family {config['family']!r} declares")
    model = {k: pub[v[1:]] if isinstance(v, str) and v.startswith("$") else v
             for k, v in mapping.items()}
    return {**model, "family": family, "config": config["name"]}


def load_cell(name: str, bench: Path = BENCH) -> dict:
    """A cell by name: its own file, its configuration's and its traffic's."""
    cell = _load("workloads", name, bench)
    config = _load("configs", cell["config"], bench)
    return {**cell, "config_file": config, "model": model_of(config, bench),
            "job": _load("traffic", cell["traffic"], bench)}


def load_metric(name: str, bench: Path = BENCH) -> dict:
    return _load("metrics", name, bench)


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = json.loads((bench / "reducers" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       "an unknown chip has no peak to be measured against")
    return table["devices"][device_kind]


def bring_up(chips: int, platform: str = "tpu") -> dict:
    """Initialise the backend in this process, hold it to ``platform`` and
    ``chips`` devices, and place the compile cache inside the checkout.
    ``platform`` is "tpu" in every real run; the CPU rehearsal in
    ``benchmark/tests`` passes "cpu"."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        platform as plat,
    )

    try:
        info = plat.select(platform)
    except (plat.PlatformUnavailable, RuntimeError) as e:
        raise NoAccelerator(str(e)) from e
    if info["n_devices"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{info['n_devices']}")
    plat.compile_cache()
    # small helper programs (norms, weight making) are worth caching too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"platform": info["platform"], "kind": info["device_kind"],
            "count": chips, "devices": jax.devices()[:chips]}


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where the backend keeps
    no such count, as the CPU's does not)."""
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


class Profiler:
    """A ``jax.profiler`` trace over part of the window, for ``--trace 1``."""

    def __init__(self, out_dir: Path, spec: dict, enabled: bool):
        self.dir = out_dir / "xplane"
        self.start_s, self.seconds = spec["start_s"], spec["seconds"]
        self.enabled = enabled
        self.t_start = self.t_stop = None
        self.stall_s = 0.0      # host seconds spent starting and stopping

    @property
    def running(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def due_start(self, elapsed: float) -> bool:
        return (self.enabled and self.t_start is None
                and elapsed >= self.start_s)

    def due_stop(self, elapsed: float) -> bool:
        return self.running and elapsed >= self.start_s + self.seconds

    def start(self) -> None:
        import shutil

        import jax

        t = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the Python tracer stalls the host
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self.t_start = time.perf_counter()
        self.stall_s += self.t_start - t

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.stall_s += time.perf_counter() - self.t_stop

    def trace_file(self):
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        return files[-1] if files else None


def compile_seconds(ledger_events) -> float:
    """Seconds the compile ledger counted: lowering and compiling (or loading
    from the persistent cache) every named program."""
    return sum((e.get("compile_ms") or 0.0) + (e.get("lower_ms") or 0.0)
               for e in ledger_events) / 1e3


def percentile(values, q: float):
    """Nearest-rank percentile of a non-empty list."""
    vals = sorted(values)
    return vals[min(len(vals) - 1, max(0, int(round(q / 100 * len(vals)
                                                       + 0.5)) - 1))]


_T_FIRST = time.perf_counter()


def mark(label: str) -> None:
    """A phase boundary on stderr, with the seconds since the harness was
    imported: where set-up goes is read from these lines."""
    say(f"[{time.perf_counter() - _T_FIRST:8.2f} s] {label}")


def say(msg: str) -> None:
    """An earlier line of the output: anything but the result."""
    print(msg, file=sys.stderr, flush=True)


def print_checks(checks: list) -> None:
    """Each number compared beside its limit, as the last lines on stderr."""
    for c in checks:
        say(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
