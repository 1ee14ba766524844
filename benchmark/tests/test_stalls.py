"""The stall rows (``reducers/stalls.py``): on hand-made observations, and
through a whole toy run of each kind, steady and with one sleep planted where
the program waits (``land`` in serving, the loader in training).

The rows and their two toy cells are files alone (``metrics/stall_*``,
``tests/data/workloads/tiny-*-stalls.json``); no cell of ``BENCHMARK.json``
lists them yet.
"""

import time
from types import SimpleNamespace

import pytest

SLEEP_S = 0.6


def _stall(loop, t_perf, dur, excess, **attrs):
    return ("stall", 1e9 + t_perf, dur,
            {"loop": loop, "n": 7, "t_perf": t_perf, "excess_s": excess,
             "where": "land", "cause": "waiting", **attrs})


def test_rows_on_hand_made_observations():
    from benchmark.reducers import stalls

    tick = ("decode", 1e9, 0.01, {"tick": 1})
    rows = lambda obs, loop: (                              # noqa: E731
        stalls.per_second(obs, None, None, loop),
        stalls.longest_ms(obs, None, None, loop))
    # no span at all: nothing to read; spans and no stall: a clean zero
    assert rows({"spans": [], "window_s": 10.0}, "serve_tick") == (None, None)
    assert rows({"spans": [tick], "window_s": 10.0}, "serve_tick") == (0.0,
                                                                       0.0)
    one = {"spans": [tick, _stall("serve_tick", 50.0, 2.04, 2.03)],
           "window_s": 10.0}
    assert rows(one, "serve_tick") == (pytest.approx(203.0),
                                       pytest.approx(2040.0))
    assert rows(one, "train_step") == (0.0, 0.0)
    mixed = {"spans": [tick, _stall("serve_tick", 50.0, 2.0, 1.9),
                       _stall("train_step", 60.0, 3.0, 2.9),
                       _stall("serve_tick", 70.0, 0.5, 0.4)],
             "window_s": 20.0}
    assert rows(mixed, "serve_tick") == (pytest.approx(115.0),
                                         pytest.approx(2000.0))
    assert rows(mixed, "train_step") == (pytest.approx(145.0),
                                         pytest.approx(3000.0))
    # the lap in which the harness started or stopped its own profiler is
    # the harness's: left out
    traced = dict(mixed, profiler=SimpleNamespace(t_start=51.5, t_stop=62.9))
    assert rows(traced, "serve_tick") == (pytest.approx(20.0),
                                          pytest.approx(500.0))
    assert rows(traced, "train_step") == (0.0, 0.0)
    idle = dict(mixed, profiler=SimpleNamespace(t_start=None, t_stop=None))
    assert rows(idle, "serve_tick") == rows(mixed, "serve_tick")


def _values(cell, dev, res, bench_dir):
    from benchmark import run as runner

    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    return {k: v["value"] for k, v in metrics.items()}, metrics


@pytest.mark.parametrize("name,kind,seconds", [
    ("tiny-serve-stalls", "serve", 1.5), ("tiny-train-stalls", "train", 1.0)])
def test_a_steady_toy_run_reports_zero(run_cell, bench_dir, name, kind,
                                       seconds):
    cell, dev, res = run_cell(name, seconds=seconds)
    assert res["correct"], res["checks"]
    value, metrics = _values(cell, dev, res, bench_dir)
    assert set(value) == {"compile_s", f"stall_ms_per_s.{kind}",
                          f"stall_longest_ms.{kind}"}
    assert value[f"stall_ms_per_s.{kind}"] == 0.0
    assert value[f"stall_longest_ms.{kind}"] == 0.0
    assert metrics[f"stall_ms_per_s.{kind}"]["unit"] == "ms/s"
    assert metrics[f"stall_longest_ms.{kind}"]["unit"] == "ms"


def _sleep_in_land(at_tick):
    """``tamper`` of the serving harness: one sleep inside a ``land`` span,
    in the first landing from tick ``at_tick`` on."""
    def tamper(sched):
        from neural_networks_parallel_training_with_mpi_tpu.train import (
            trace as trace_lib,
        )

        land, state = sched.server.land, {"done": False}

        def slow(every=False):
            if not state["done"] and sched.tick_no >= at_tick:
                state["done"] = True
                with trace_lib.span("land"):
                    time.sleep(SLEEP_S)
            return land(every)

        sched.server.land = slow

    return tamper


def _sleep_in_the_loader(at_batch):
    """``tamper`` of the training harness: the loader takes ``SLEEP_S`` over
    its batch number ``at_batch``, inside the ``load`` span's ``next()``."""
    def tamper(trainer):
        epoch_of, state = trainer.loader.epoch, {"n": 0}

        def epoch(e, start_step=0):
            for batch in epoch_of(e, start_step=start_step):
                state["n"] += 1
                if state["n"] == at_batch:
                    time.sleep(SLEEP_S)
                yield batch

        trainer.loader.epoch = epoch

    return tamper


@pytest.mark.parametrize("name,kind,tamper,where", [
    ("tiny-serve-stalls", "serve", _sleep_in_land(60), "land"),
    ("tiny-train-stalls", "train", _sleep_in_the_loader(30), "load")])
def test_a_planted_sleep_is_reported_to_within_a_fifth(run_cell, bench_dir,
                                                       name, kind, tamper,
                                                       where):
    cell, dev, res = run_cell(name, seconds=4.0, tamper=tamper)
    assert res["correct"], res["checks"]
    stalls = [s for s in res["obs"]["spans"] if s[0] == "stall"]
    assert [s[3]["where"] for s in stalls].count(where) == 1, stalls
    value, _ = _values(cell, dev, res, bench_dir)
    extra = sum(s[3]["excess_s"] for s in stalls if s[3]["where"] != where)
    planted_ms_per_s = 1e3 * SLEEP_S / res["obs"]["window_s"]
    assert (value[f"stall_ms_per_s.{kind}"]
            - 1e3 * extra / res["obs"]["window_s"]
            == pytest.approx(planted_ms_per_s, rel=0.2))
    if not extra:
        assert value[f"stall_longest_ms.{kind}"] == pytest.approx(
            1e3 * SLEEP_S, rel=0.2)
