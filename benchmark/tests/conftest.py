"""CPU rehearsal of the benchmark: four virtual devices, tiny toy cells.

Run with ``python -m pytest benchmark/tests -q`` (not part of tier-1).  The
toys live in ``tests/data`` as the same kinds of file a real cell is made of;
``bench_dir`` overlays them on a temporary copy of ``benchmark/``, which is
also the proof that a cell, a configuration, a mix, a metric and a family are
added as files, with no edit to a file that is there.
"""

import os
import shutil
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(scope="session")
def bench_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "traffic", "workloads", "metrics", "reducers",
                "families"):
        shutil.copytree(BENCH / sub, tmp / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
        overlay = BENCH / "tests" / "data" / sub
        if overlay.is_dir():
            for f in (f for f in overlay.iterdir() if f.is_file()):
                assert not (tmp / sub / f.name).exists(), f"{f.name} edits"
                shutil.copy(f, tmp / sub / f.name)
    return tmp


@pytest.fixture(scope="session")
def cpu_dev():
    from benchmark.harness import common

    def make(chips):
        return common.bring_up(chips, "cpu")

    return make


@pytest.fixture
def run_cell(bench_dir, cpu_dev, tmp_path):
    """Drive everything of a run but the look for a chip."""
    import importlib

    from benchmark.harness import common

    def go(name, seed=7, seconds=0.6, trace=False, **kw):
        cell = common.load_cell(name, bench_dir)
        dev = cpu_dev(cell["chips"])
        kind = importlib.import_module(
            f"benchmark.harness.{cell['job']['kind']}")
        res = kind.run(cell, seed, seconds, trace, dev, time.perf_counter(),
                       out_dir=tmp_path / "out", **kw)
        return cell, dev, res

    return go
