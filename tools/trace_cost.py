#!/usr/bin/env python3
"""What the span tracer and the lap watch cost, in microseconds.

    python tools/trace_cost.py [--n 100000] [--repeats 5] [--package DIR]

Times, in this process and on this host's clock: a span with no tracer
installed; a span under an installed ``Tracer`` that no loop laps under (it
writes each record as it is made); a bare lap of a ``LapWatch``, and the read
of the OS's counters that one lap in every fifth of the stall floor makes on
top; and a lap that holds ten spans under an installed tracer, the shape of
a scheduler tick, per lap and per span.  ``--package`` names a checkout to import the
program from (a parent commit's, to compare: one that has no ``LapWatch``
reports its spans alone).  Each figure is the least of ``--repeats`` loops of
``--n``; one JSON line.  No accelerator is touched.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path


def least(fn, repeats: int) -> float:
    return min(fn() for _ in range(repeats))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--package", default=str(Path(__file__).resolve()
                                             .parents[1]))
    args = ap.parse_args(argv)
    sys.path.insert(0, args.package)
    from neural_networks_parallel_training_with_mpi_tpu.train import trace

    n = args.n

    def spans() -> float:
        t = time.perf_counter()
        for i in range(n):
            with trace.span("decode", tick=i):
                pass
        return (time.perf_counter() - t) / n * 1e6

    out = {"package": args.package, "n": n,
           "span_us.no_tracer": least(spans, args.repeats)}
    watch_cls = getattr(trace, "LapWatch", None)

    def laps() -> float:
        watch = watch_cls("cost")
        t = time.perf_counter()
        for i in range(n):
            watch.lap(i)
        return (time.perf_counter() - t) / n * 1e6

    def ticks() -> float:
        watch = watch_cls("cost") if watch_cls else None
        t = time.perf_counter()
        for i in range(n // 10):
            if watch is not None:
                watch.lap(i)
            for _ in range(10):
                with trace.span("decode", tick=i):
                    pass
        dt = time.perf_counter() - t
        if watch is not None:
            watch.end()
        return dt / (n // 10) * 1e6

    def os_readings() -> float:
        t = time.perf_counter()
        for _ in range(n):
            trace._readings()
        return (time.perf_counter() - t) / n * 1e6

    if watch_cls is not None:
        out["lap_us.no_tracer"] = least(laps, args.repeats)
        # what the lap that reads the OS's counters pays on top (one in
        # every fifth of the stall floor)
        out["os_readings_us"] = least(os_readings, args.repeats)
    with tempfile.TemporaryDirectory() as d:
        # max_events above what the loops make: a dropped record is cheaper
        tracer = trace.Tracer(d, 0, "cost", 0,
                              max_events=4 * n * args.repeats)
        trace.install(tracer)
        try:
            out["span_us.tracer"] = least(spans, args.repeats)
            tick_us = least(ticks, args.repeats)
            out["tick_of_10_spans_us.tracer"] = tick_us
            out["span_us.tracer_in_tick"] = tick_us / 10
        finally:
            trace.install(None)
            tracer.close()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
