"""Tracing / profiling (extension — SURVEY.md §5.1: the reference has no
timers or profiler hooks, only ``print``).

Two tools, both zero-cost when disabled (named regions inside the
capture are ``train/trace.py`` spans, mirrored as ``nnpt:<name>``):

* :func:`trace` — leader-only ``jax.profiler`` trace context writing a
  TensorBoard/XProf-compatible trace of device + host activity.
* :class:`StepTimer` — host-side per-step wall-clock stats (p50/p95/max,
  steps/sec) measured the async-dispatch-friendly way: the timer never
  forces a device sync itself; call ``tick()`` once per dispatched step
  and ``block()`` at measurement boundaries.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax

from .logging import is_leader


@contextlib.contextmanager
def trace(log_dir: Optional[str], leader_only: bool = True):
    """Profiler trace context; no-op if ``log_dir`` is falsy (or on
    non-leader processes with ``leader_only``)."""
    if not log_dir or (leader_only and not is_leader()):
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Per-device live/peak memory where the backend reports it (TPU does;
    CPU returns {})."""
    out: Dict[str, Dict[str, int]] = {}
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", lambda: None)()
        if stats:
            out[str(d)] = {k: int(v) for k, v in stats.items()
                           if isinstance(v, (int, float))}
    return out


def donation_report(compiled, hlo_text: Optional[str] = None
                    ) -> Dict[str, Any]:
    """Inspect a compiled executable's buffer-donation result (ROADMAP
    item 2's donation audit): parse the ``input_output_alias`` (donations
    the compiler ACCEPTED — each aliased output reuses its input buffer,
    no copy) and ``buffer_donor`` (donations offered but NOT aliased to
    any output — the donated buffer is freed, but the matching output is
    a fresh allocation, i.e. an unexpected copy) annotations from the
    optimized HLO's module header.

    ``compiled`` is the object returned by ``jitted.lower(...).compile()``.
    Returns ``{"aliased": [(output_index, param_number), ...],
    "n_aliased": ..., "unaliased_donors": n}``.  A step that donates its
    TrainState should alias every donatable state leaf; a refactor that
    silently breaks donation (e.g. a dtype change on one side of the
    in/out pair) shows up as leaves migrating from ``aliased`` to
    ``unaliased_donors`` — the regression tests pin the counts.

    ``hlo_text``: pass the module text if the caller already rendered it
    (``compiled.as_text()`` re-stringifies the WHOLE optimized module —
    tens of MB at transformer scale — just to read its header line)."""
    import re

    if hlo_text is None:
        hlo_text = compiled.as_text()
    header = hlo_text.split("\n", 1)[0]
    # entries look like `{1}: (3, {}, may-alias)` inside
    # input_output_alias={...}: output tuple-index {1} aliases param 3
    aliased = [(tuple(int(x) for x in out_idx.split(",") if x.strip()),
                int(param))
               for out_idx, param in re.findall(
                   r"\{([0-9, ]*)\}:\s*\((\d+),", header)]
    donors = 0
    md = re.search(r"buffer_donor=\{(.*?)\}\s*,\s*entry_computation", header)
    if md is None:
        md = re.search(r"buffer_donor=\{(.*?)\}\s*$", header)
    if md:
        donors = len(re.findall(r"\(\d+,", md.group(1)))
    return {"aliased": aliased, "n_aliased": len(aliased),
            "unaliased_donors": donors}


class StepTimer:
    """Wall-clock per-step statistics.

    Under async dispatch a ``tick()`` measures dispatch-to-dispatch time,
    which converges to true step time once the pipeline is saturated —
    without inserting any ``block_until_ready`` into the hot loop (the
    reference blocks every step by construction, :185)."""

    def __init__(self, skip_first: int = 1):
        self.skip_first = skip_first
        self._times: List[float] = []
        self._last: Optional[float] = None
        self._seen = 0

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._seen += 1
            if self._seen > self.skip_first:
                self._times.append(now - self._last)
        self._last = now

    def block(self, value: Any) -> Any:
        """Block on a step output at a measurement boundary and restart the
        interval clock (so the sync isn't charged to the next step)."""
        value = jax.block_until_ready(value)
        self._last = time.perf_counter()
        return value

    @staticmethod
    def _pct(sorted_times: List[float], q: float) -> float:
        if not sorted_times:
            return float("nan")
        i = min(len(sorted_times) - 1, int(q * (len(sorted_times) - 1)))
        return sorted_times[i]

    def stats(self) -> Dict[str, float]:
        ts = sorted(self._times)
        if not ts:
            return {}
        return {
            "step_time_p50_ms": 1e3 * self._pct(ts, 0.50),
            "step_time_p95_ms": 1e3 * self._pct(ts, 0.95),
            "step_time_max_ms": 1e3 * ts[-1],
            "steps_per_sec": len(ts) / sum(ts),
        }
