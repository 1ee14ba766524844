"""Every data file parses, keeps to the allowed characters, and agrees with
``BENCHMARK.json``."""

import json
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def files(kind):
    return sorted((BENCH / kind).glob("*.json"))


@pytest.mark.parametrize("path", [p for k in ("configs", "traffic",
                                               "workloads", "metrics")
                                  for p in files(k)], ids=lambda p: p.name)
def test_file_parses_and_is_named_for_itself(path):
    d = json.loads(path.read_text())
    assert d["name"] == path.stem
    assert NAME.match(d["name"])


@pytest.mark.parametrize("path", files("metrics"), ids=lambda p: p.name)
def test_metric_file(path):
    m = json.loads(path.read_text())
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    mod, fn = m["reducer"].split(":")
    import importlib

    assert callable(getattr(
        importlib.import_module(f"benchmark.reducers.{mod}"), fn))


def test_manifest_agrees_with_the_files():
    from benchmark.harness import common

    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    per = {m["name"]: m for m in MANIFEST["per_layer"]}
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    for name, w in cells.items():
        cell = common.load_cell(name)
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        assert len(w["why"]) <= 200
        reports = {n for n, m in e2e.items()
                   if name in m.get("workloads", cells)}
        assert "setup_s" in reports and len(reports) >= 2
        assert cell["per_layer"], "a cell reports a per-layer metric"
        for metric in cell["per_layer"]:
            spec = common.load_metric(metric)
            assert name in per[metric]["workloads"]
            assert per[metric]["moves"] == spec["moves"] in reports
            for key in ("unit", "better", "source", "layer"):
                assert per[metric][key] == spec[key]
    for metric, m in per.items():
        for name in m["workloads"]:
            assert metric in common.load_cell(name)["per_layer"]
    for c in MANIFEST["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert (f["source"], f["reduced"]) == (c["source"], c["reduced"])
        assert set(common.load_family(f["family"]).MODEL_KEYS) \
            <= set(f["mapping"])
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_a_cell_is_added_as_files_alone(bench_dir):
    """The toys of tests/data are a configuration, a mix, a cell, a metric
    and a family that ``benchmark/`` has not: ``bench_dir`` adds them without
    touching a file that is there, and the harness finds each by its name."""
    from benchmark.harness import common

    cell = common.load_cell("tiny-train", bench_dir)
    assert cell["model"]["d_model"] == 64 and cell["job"]["kind"] == "train"
    assert common.load_metric("tiny_fetch_ms.train", bench_dir)["args"] \
        == {"span": "fetch"}
    assert common.load_cell("gpt2m-train-b4", bench_dir)["model"]["d_model"] \
        == 1024
    switch = common.load_cell("tiny-switch-serve", bench_dir)["model"]
    assert switch["family"].__name__ == "benchmark.families.switch_toy"
    assert "n_experts" in switch["family"].MODEL_KEYS
    assert not (BENCH / "families" / "switch_toy.py").exists()


def test_unknown_device_has_no_peak():
    from benchmark.harness import common

    assert common.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        common.peaks("cpu")
