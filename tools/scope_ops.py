#!/usr/bin/env python3
"""The executed operations under one ``jax.named_scope``, by self time.

    JAX_PLATFORMS=cpu python tools/scope_ops.py <scope> [module] [run dir]

Reads the newest ``*.xplane.pb`` under the run directory of
``benchmark/run.py --trace 1`` (default ``benchmark/out``) and prints one
JSON object: the operations whose ``op_name`` path holds ``scope`` (``-`` for
the operations that carry no path at all), inside the executions of the
program whose module name matches ``module`` (default: any), grouped by the
head of their HLO text (name, shape and opcode), with their count, their self
seconds and their share of the scope.  ``tools/scope_report.py`` says how much
time a scope holds; this says what the compiler put there (PERF.md section 6,
PR 30: what sat under ``paged_gather``).
"""

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

_HEAD = re.compile(r"^(%?[\w.\-]+) = (\S+) ([\w\-]+)\(")


def head_of(hlo: str) -> str:
    """``<shape> <opcode>`` (a fusion keeps its kind) of an HLO text."""
    m = _HEAD.match(hlo)
    if not m:
        return hlo[:80]
    kind = re.search(r"kind=(k\w+)", hlo)
    return f"{m.group(2)} {m.group(3)}" + (f":{kind.group(1)}" if kind else "")


def ops_under(path, scope: str, module: str = "", top: int = 25) -> dict:
    from benchmark.reducers import scopes, xplane, xspace

    space = xspace.read(path, xplane.DEVICE_PLANE,
                        lines=(xplane.OPS_LINE, xplane.MODULES_LINE))
    plane = sorted(space)[0]
    found = space[plane]
    meta = found["metadata"]
    rx = None if scope == "-" else scopes.component(scope)
    mod = re.compile(module)
    runs = xplane.merged(
        (s, s + d) for m, s, d in found["lines"].get(xplane.MODULES_LINE, [])
        if mod.search(meta[m]["name"]))
    n_runs = sum(1 for m, _s, _d in found["lines"].get(xplane.MODULES_LINE, [])
                 if mod.search(meta[m]["name"]))
    # self time keyed by the metadata id, which tells two operations of one
    # path apart (``scopes.self_times`` carries its first field through)
    rows, total = {}, 0.0
    for mid, start, own in scopes.self_times(
            found["lines"].get(xplane.OPS_LINE, [])):
        path_of = meta[mid].get("tf_op", "")
        if (rx.search(path_of) if rx else not path_of) and any(
                a <= start < b for a, b in runs):
            key = head_of(meta[mid]["name"])
            row = rows.setdefault(key, {"count": 0, "self_s": 0.0,
                                        "op_name": path_of[-120:]})
            row["count"] += 1
            row["self_s"] += own / 1e9
            total += own / 1e9
    ranked = sorted(rows.items(), key=lambda kv: -kv[1]["self_s"])[:top]
    return {"trace": str(path), "scope": scope, "module": module,
            "executions": n_runs, "self_s": total,
            "ms_per_execution": 1e3 * total / n_runs if n_runs else None,
            "ops": [{"op": k, **v, "share": v["self_s"] / total if total
                     else 0.0} for k, v in ranked]}


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    scope = argv[1]
    module = argv[2] if len(argv) > 2 else ""
    out_dir = Path(argv[3]) if len(argv) > 3 else ROOT / "benchmark" / "out"
    traces = sorted(out_dir.glob("xplane/plugins/profile/*/*.xplane.pb"))
    if not traces:
        print(f"no trace under {out_dir}", file=sys.stderr)
        return 1
    print(json.dumps(ops_under(traces[-1], scope, module)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
