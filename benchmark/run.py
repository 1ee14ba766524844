#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is looked up by name: ``workloads/<cell>.json`` names its configuration
(``configs/``), its traffic (``traffic/``) and the per-layer metrics it reports
(``metrics/``).  The traffic's ``kind`` names the module of ``harness/`` that
runs the window; a metric's ``reducer`` names ``<module>:<function>`` in
``reducers/``.  The last line of standard output is the result, one JSON object.
There is no CPU path: without the chips the cell asks for the run exits 3 and
prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def per_layer_metrics(cell: dict, res: dict, dev: dict, bench=None) -> dict:
    """Each of the cell's per-layer metrics through its reducer; one that
    finds nothing to read is left out."""
    from benchmark.harness import common

    out = {}
    for name in cell["per_layer"]:
        spec = common.load_metric(name, bench or common.BENCH)
        mod, fn = spec["reducer"].split(":")
        reducer = getattr(importlib.import_module(f"benchmark.reducers.{mod}"),
                          fn)
        value = reducer(res["obs"], cell, dev, **spec["args"])
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def result_line(cell: dict, res: dict, dev: dict, trace: bool) -> dict:
    from benchmark.harness import common
    from benchmark.reducers import xplane

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if trace:
        line["metrics"] = per_layer_metrics(cell, res, dev)
        parsed = xplane.trace_of(res["obs"])
        if parsed and parsed["devices"]:
            device["busy_s"] = xplane.busy_seconds(parsed)
            device["window_s"] = xplane.traced_window_s(res["obs"])
            line["breakdown"] = xplane.breakdown(res["obs"])
    else:
        units = {m["name"]: m["unit"] for m in json.loads(
            (common.ROOT / "BENCHMARK.json").read_text())["end_to_end"]}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in res["end_to_end"].items()}
    line["device"] = device
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in res["checks"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import common

    cell = common.load_cell(args.workload)
    try:
        dev = common.bring_up(cell["chips"])
    except common.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    common.mark(f"backend up: {dev['count']} x {dev['kind']}")
    kind = importlib.import_module(f"benchmark.harness.{cell['job']['kind']}")
    res = kind.run(cell, args.seed, args.seconds, bool(args.trace), dev,
                   T_PROCESS)
    line = result_line(cell, res, dev, bool(args.trace))
    common.print_checks(res["checks"])
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
