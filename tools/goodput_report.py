"""Goodput ledger report: where did every fleet second go?

Joins one or more trace directories' span streams with the supervisor
lifecycle events (``supervisor-events*.jsonl``) and autopilot decision
ledger (``autopilot*.jsonl``) into the exact offline goodput account
built by ``utils/goodput.py``: every second of each process's covered
wall-clock lands in exactly one category of the fixed set (step,
compile, data_stall, ckpt, rollback, eval, relaunch_gap, drain,
serve_queue_wait, serve_bubble, idle), gaps attributed rather than
dropped, categories provably summing to the covered interval.

Renders a per-process ledger (per-incarnation rows with exit codes and
relaunch gaps priced) and the fleet-wide rollup with a category bar.
Zero dependencies beyond the stdlib — proven under ``python -S`` like
``ckpt_fsck``/``trace_report``/``obs_agg``, so a trace bundle copied
off a pod is triageable on a host with no JAX::

    python tools/goodput_report.py RUN_DIR
    python tools/goodput_report.py RUN_A RUN_B --json
    python tools/goodput_report.py RUN_DIR --min-seconds 0.01
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import sys
from typing import Any, Dict, List, Optional

_UTILS_DIR = (pathlib.Path(__file__).resolve().parent.parent
              / "neural_networks_parallel_training_with_mpi_tpu"
              / "utils")


def _load_mod(name: str, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


jz = _load_mod("_nnpt_jsonl", _UTILS_DIR / "jsonl.py")
gp = _load_mod("_nnpt_goodput", _UTILS_DIR / "goodput.py")
gp._jsonl = jz  # standalone load: inject the shared tolerant reader

_BAR_W = 40
# one glyph per category for the text bar, in CATEGORIES order
_GLYPH = {"step": "#", "compile": "C", "data_stall": "d", "ckpt": "k",
          "rollback": "R", "eval": "e", "recovery": "r",
          "relaunch_gap": "_", "drain": "v",
          "serve_queue_wait": "q", "serve_bubble": "b", "idle": "."}


def _bar(categories: Dict[str, float], covered: float,
         width: int = _BAR_W) -> str:
    """Proportional category bar: '####CC..' — largest-remainder fill
    so the glyph count always equals ``width``."""
    if covered <= 0:
        return "-" * width
    shares = [(c, categories.get(c, 0.0) / covered * width)
              for c in gp.CATEGORIES]
    cells = {c: int(s) for c, s in shares}
    rem = width - sum(cells.values())
    for c, s in sorted(shares, key=lambda kv: -(kv[1] - int(kv[1]))):
        if rem <= 0:
            break
        cells[c] += 1
        rem -= 1
    return "".join(_GLYPH[c] * cells[c] for c in gp.CATEGORIES)


def _fmt_cats(categories: Dict[str, float], covered: float,
              min_seconds: float) -> str:
    parts = []
    for c in gp.CATEGORIES:
        v = categories.get(c, 0.0)
        if v < min_seconds:
            continue
        pct = (v / covered * 100.0) if covered > 0 else 0.0
        parts.append(f"{c} {v:.3f}s ({pct:.1f}%)")
    return ", ".join(parts) if parts else "(empty)"


def render(ledger: Dict[str, Any], min_seconds: float = 1e-4) -> str:
    lines: List[str] = []
    fleet = ledger.get("fleet") or {}
    for row in ledger.get("processes") or []:
        run = row.get("run") or "?"
        covered = row.get("covered_s") or 0.0
        frac = row.get("goodput_fraction")
        lines.append(
            f"process p{row.get('p')} run {run}: "
            f"{covered:.3f}s covered, goodput "
            + (f"{frac * 100:.1f}%" if frac is not None else "?")
            + ("" if row.get("sum_ok")
               else f"  [SUM MISMATCH residual={row.get('sum_residual_s')}s]"))
        lines.append("  [" + _bar(row.get("categories") or {}, covered)
                     + "]")
        lines.append("  " + _fmt_cats(row.get("categories") or {},
                                      covered, min_seconds))
        for ir in row.get("incarnations") or []:
            rc = ir.get("exit_rc")
            lines.append(
                f"    inc {ir.get('inc')}: {ir.get('covered_s'):.3f}s, "
                f"{ir.get('n_spans')} span(s)"
                + (f", exit rc={rc}" if rc is not None else ""))
    lines.append("")
    covered = fleet.get("covered_s") or 0.0
    frac = fleet.get("goodput_fraction")
    lines.append(
        f"fleet: {fleet.get('n_processes', 0)} process(es), "
        f"{covered:.3f}s covered, goodput "
        + (f"{frac * 100:.1f}%" if frac is not None else "?")
        + f", {fleet.get('relaunches', 0)} relaunch(es), "
        f"{fleet.get('decisions', 0)} autopilot decision(s)"
        + (f", {fleet.get('preempt_notices', 0)} preemption "
           "notice(s)" if fleet.get("preempt_notices") else "")
        + ("" if fleet.get("sum_ok") else "  [SUM MISMATCH]"))
    lines.append("  [" + _bar(fleet.get("categories") or {}, covered)
                 + "]")
    lines.append("  " + _fmt_cats(fleet.get("categories") or {},
                                  covered, min_seconds))
    legend = "  ".join(f"{_GLYPH[c]}={c}" for c in gp.CATEGORIES)
    lines.append(f"  legend: {legend}")
    if fleet.get("preempt_notices"):
        # crash-vs-notice reading aid: an announced preemption (exit
        # rc=47 after a notice) prices its tail as 'drain' — the
        # crash categories 'rollback' and 'relaunch_gap' staying at
        # zero is the advance-notice win, not an accounting gap
        lines.append("  note: advance-notice exits (rc=47) price "
                     "their tail as drain; rollback/relaunch_gap at "
                     "zero is the announced-preemption contract")
    skipped = fleet.get("lines_skipped")
    if skipped:
        lines.append(f"  note: {skipped} unparseable JSONL line(s) "
                     "skipped (torn tail of a killed writer)")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dirs", nargs="+",
                    help="trace dirs (trace-*.jsonl + optional "
                         "supervisor-events*.jsonl / autopilot*.jsonl)")
    ap.add_argument("--json", action="store_true",
                    help="print the raw ledger document as JSON")
    ap.add_argument("--min-seconds", type=float, default=1e-4,
                    metavar="S",
                    help="hide categories below this many seconds in "
                         "the text rendering (default: 1e-4)")
    args = ap.parse_args(argv)

    missing = [d for d in args.dirs if not os.path.isdir(d)]
    if missing:
        print(f"ERROR: not a directory: {', '.join(missing)}",
              file=sys.stderr)
        return 2

    # merge the inputs of every dir into ONE ledger: a fleet is one
    # time account, not a per-dir report
    records: List[Dict[str, Any]] = []
    sup_events: List[Dict[str, Any]] = []
    decisions: List[Dict[str, Any]] = []
    skipped = 0
    for d in args.dirs:
        inputs = gp.collect_dir(d)
        records.extend(inputs["records"])
        sup_events.extend(inputs["sup_events"])
        decisions.extend(inputs["decisions"])
        skipped += inputs["skipped"]
    ledger = gp.build_ledger(records, sup_events, decisions)
    ledger["fleet"]["lines_skipped"] = skipped

    if args.json:
        print(json.dumps(ledger, indent=2))
    else:
        print(render(ledger, min_seconds=args.min_seconds))
    bad = [r for r in ledger["processes"] if not r.get("sum_ok")]
    if bad or not ledger["fleet"].get("sum_ok", True):
        return 1  # the invariant is the product — failing it is an error
    return 0


if __name__ == "__main__":
    sys.exit(main())
