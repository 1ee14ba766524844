"""Traffic kind ``train``: a window of optimizer steps through ``Trainer.fit``.

The trainer is built from the flags ``cli.main`` parses, on a ``data`` mesh of
the cell's chips, over tokens and weights the benchmark makes from the seed.
``fit`` has no clock, so the harness stands outside it: a span listener
(``train/trace.add_listener``) sees every ``dispatch`` close, lets the first
steps go by as warm-up (they are the steps the reference follows), opens the
window when they are done, and at its end waits for the last step's state and
raises SIGTERM in this process, which is the stop ``fit`` offers its users.
"""

from __future__ import annotations

import json
import math
import shutil
import signal
import time

import numpy as np

from . import check, common, weights


def make_tokens(seed: int, rows: int, seq_len: int, vocab: int) -> dict:
    """Uniform random token rows (every row differs); ``y`` is ``x`` shifted."""
    rng = np.random.default_rng([int(seed), 7])
    toks = rng.integers(0, vocab, size=(rows, seq_len + 1), dtype=np.int32)
    return {"x": toks[:, :-1].copy(), "y": toks[:, 1:].copy()}


class FitDriver:
    """The listener that times ``Trainer.fit`` from outside."""

    def __init__(self, trainer, cell, seed, seconds, profiler, t_process):
        self.trainer, self.cell, self.seed = trainer, cell, seed
        self.seconds, self.profiler = seconds, profiler
        self.t_process = t_process
        self.warmup = cell["job"]["warmup_steps"]
        self.dispatched = 0
        self.spans = []              # (name, t_unix, dur_s, attrs) in window
        self.error = None
        self.t0 = self.t_end = self.setup_s = None
        self.steps_in_window = 0
        self.traced_steps = 0
        self.grad_sq = self.change_sq = None
        self.ledger = self.compiles_at_t0 = None
        self.stopped = False

    # -- small jitted reads of the state, by the family's leaf names ----------
    def _leaf_sq(self, tree):
        import jax
        import jax.numpy as jnp

        model = self.cell["model"]
        return jax.jit(lambda t: {
            n: (x.astype(jnp.float32) ** 2).sum()
            for n, x in flat_names(model, t).items()})(tree)

    def _change_sq(self, params):
        """Squared norm of every leaf's change from the start, which is made
        again from the seed one layer at a time (the step donated it)."""
        import jax
        import jax.numpy as jnp

        model = self.cell["model"]
        fam = model["family"]
        maker = weights.Maker(model, self.seed,
                              jax.tree_util.tree_leaves(params)[0].sharding)
        sq = lambda a, b: {n: ((a[n].astype(jnp.float32)        # noqa: E731
                                - b[n].astype(jnp.float32)) ** 2).sum()
                           for n in a}
        layer = jax.jit(lambda a, b: sq(fam.layer_leaves(model, a),
                                        fam.layer_leaves(model, b)))
        outer = jax.jit(lambda a, b: sq(fam.outer_leaves(model, a),
                                        fam.outer_leaves(model, b)))
        mine_outer, mine_layers = fam.split_program(model, params)
        out = outer(mine_outer, fam.to_program_outer(model, maker.outer()))
        for i, blk in enumerate(mine_layers):
            mine = layer(blk, fam.to_program_layer(model, maker.layer(i), i))
            out.update({f"L{i}.{n}": x for n, x in mine.items()})
        return out

    def on_span(self, name, t_unix, dur_s, attrs):
        if self.error is not None or self.stopped:
            return
        try:
            if self.t0 is not None:
                self.spans.append((name, t_unix, dur_s, dict(attrs or {})))
            if name == "dispatch":
                self._on_dispatch()
        except BaseException as e:      # the tracer swallows exceptions
            self.error = e
            self._stop()

    def _stop(self):
        self.stopped = True
        signal.raise_signal(signal.SIGTERM)

    def _on_dispatch(self):
        import jax

        from neural_networks_parallel_training_with_mpi_tpu.utils import (
            compile_ledger,
        )

        self.dispatched += 1
        state = self.trainer.state
        if self.dispatched == 1:
            common.mark("first step dispatched (compiled or loaded)")
            self.grad_sq = self._leaf_sq(state.opt_state.mu)
        if self.dispatched == self.warmup:
            jax.block_until_ready(state.params)
            common.mark(f"{self.warmup} warm-up steps done")
            self.change_sq = jax.device_get(self._change_sq(state.params))
            self.grad_sq = jax.device_get(self.grad_sq)
            self.ledger = compile_ledger.active()
            self.compiles_at_t0 = len(self.ledger.events)
            self.t0 = time.perf_counter()
            self.setup_s = self.t0 - self.t_process
            common.mark("window opens")
            return
        if self.t0 is None:
            return
        self.steps_in_window += 1
        if self.profiler.running:
            self.traced_steps += 1
        elapsed = time.perf_counter() - self.t0
        if self.profiler.due_start(elapsed):
            jax.block_until_ready(state.step)
            self.profiler.start()
        elif self.profiler.due_stop(elapsed):
            jax.block_until_ready(state.step)
            self.profiler.stop()
        if elapsed >= self.seconds:
            jax.block_until_ready(state.step)
            self.t_end = time.perf_counter()
            if self.profiler.running:
                self.profiler.stop()
            self._stop()


def flat_names(model: dict, tree) -> dict:
    """A tree shaped like the program's parameters -> {leaf name: leaf}, with
    the names ``reference.train.leaf_norms`` gives (``L3.attn_out.w``)."""
    fam = model["family"]
    outer, layers = fam.split_program(model, tree)
    out = dict(fam.outer_leaves(model, outer))
    for i, blk in enumerate(layers):
        out.update({f"L{i}.{n}": x
                    for n, x in fam.layer_leaves(model, blk).items()})
    return out


def build_trainer(cell: dict, seed: int, devices, out_dir, extra_flags=()):
    """The trainer as a user's command line builds it, with the benchmark's
    tokens and weights in place of the program's own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        MeshConfig, build_argparser, config_from_args,
    )
    from neural_networks_parallel_training_with_mpi_tpu.ops import qmm
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.state import (
        TrainState,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    model, job = cell["model"], cell["job"]
    fam = model["family"]
    flags = fam.train_flags(model, job, seed, out_dir) + list(extra_flags)
    cfg = config_from_args(build_argparser().parse_args(flags))
    mesh = make_mesh(MeshConfig(data=len(devices)), devices=list(devices))
    data = make_tokens(seed, job["global_batch"] * job["steps_of_data"],
                       job["seq_len"], model["vocab_size"])
    trainer = Trainer(cfg, mesh=mesh, data=data)
    common.mark("trainer built")
    replicated = NamedSharding(mesh, PartitionSpec())
    maker = weights.Maker(model, seed, replicated)
    params = fam.to_program(model, maker.outer(), maker.layers())
    opt_state = jax.jit(trainer.optimizer.init,
                        out_shardings=replicated)(params)
    trainer.state = jax.device_put(
        TrainState(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, qstate=qmm.init_qstate(trainer.model)),
        replicated)
    jax.block_until_ready(trainer.state.params)
    common.mark("weights and optimizer state on the device")
    return trainer, data


def read_losses(out_dir) -> list:
    path = out_dir / "train_metrics.jsonl"
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    return [r["loss"] for r in recs if "loss" in r and "step" in r]


def run(cell: dict, seed: int, seconds: float, trace: bool, dev: dict,
        t_process: float, out_dir=common.OUT, extra_flags=(), tamper=None,
        reference_kwargs=None) -> dict:
    """One run of a training cell.  ``extra_flags`` and ``tamper`` are for the
    control and the planted faults of ``benchmark/tests``: further trainer
    flags, and a function that breaks the built trainer before ``fit``."""
    import gc

    import jax

    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )

    from ..reference import train as ref_train

    shutil.rmtree(out_dir / "train_trace", ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "train_metrics.jsonl").unlink(missing_ok=True)
    model, job = cell["model"], cell["job"]
    trainer, data = build_trainer(cell, seed, dev["devices"], out_dir,
                                  extra_flags)
    if tamper is not None:
        tamper(trainer)
    profiler = common.Profiler(out_dir, job["trace"], trace)
    driver = FitDriver(trainer, cell, seed, seconds, profiler, t_process)
    trace_lib.add_listener(driver.on_span)
    try:
        trainer.fit()
    finally:
        trace_lib.remove_listener(driver.on_span)
    if driver.error is not None:
        raise driver.error
    if driver.t_end is None:
        raise RuntimeError("Trainer.fit returned before the window closed")
    events = list(driver.ledger.events)
    losses = read_losses(out_dir)
    peak = common.memory_peak_bytes(dev["devices"])
    window_s = driver.t_end - driver.t0
    traced_out = window_s - profiler.stall_s    # what per-layer rates are over
    tokens = driver.steps_in_window * job["global_batch"] * job["seq_len"]
    bad = sum(1 for x in losses if not math.isfinite(x))
    compiled_in_window = len(events) - driver.compiles_at_t0

    # ---- the program's three readings, then free it for the reference -----
    b1 = job["optimizer"]["b1"]
    prog = {"losses": losses[:driver.warmup],
            "grad_norm": {n: math.sqrt(float(v)) / (1.0 - b1)
                          for n, v in driver.grad_sq.items()},
            "change_norm": {n: math.sqrt(float(v))
                            for n, v in driver.change_sq.items()}}
    trainer.state = None
    del trainer
    gc.collect()
    gb = job["global_batch"]
    batches = [(data["x"][i * gb:(i + 1) * gb], data["y"][i * gb:(i + 1) * gb])
               for i in range(driver.warmup)]
    common.mark("window closed, program freed")
    t_ref = time.perf_counter()
    ref = ref_train.three_steps(model, job["optimizer"], seed, batches,
                                log=common.mark, **(reference_kwargs or {}))
    ref_s = time.perf_counter() - t_ref
    checks = check.train_checks(prog, ref, cell["limits"])
    checks.append(check.entry("compiles_in_window", compiled_in_window, 0))
    checks.append(check.entry("nonfinite_losses", bad, 0))
    common.say(f"train: {driver.steps_in_window} steps in {window_s:.3f} s, "
               f"{len(losses)} losses logged, last {losses[-1]:.4f}; "
               f"reference {ref_s:.1f} s; set-up {driver.setup_s:.1f} s")
    return {
        "correct": all(c["ok"] for c in checks), "checks": checks,
        "attempted": driver.steps_in_window, "failed": bad,
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": driver.setup_s},
        "memory_peak_bytes": peak,
        "obs": {"spans": driver.spans, "window_s": traced_out,
                "gap_default": "Trainer.fit (no annotation inside)",
                "traced_steps": driver.traced_steps, "tokens": tokens,
                "compile_s": common.compile_seconds(
                    events[:driver.compiles_at_t0]),
                "profiler": profiler, "readings": {"prog": prog, "ref": ref},
                "reference_s": ref_s},
    }
