"""Merge per-process span traces into one Chrome/Perfetto timeline.

Reads a ``--trace_dir`` (train/trace.py: ``trace-p{P}-i{I}.jsonl`` span
files plus ``compiles-p{P}-i{I}.jsonl`` compile-ledger files, one pair
per process × incarnation) and writes:

* ``trace.json`` — Chrome trace format (load it in Perfetto's
  https://ui.perfetto.dev or chrome://tracing): every (process,
  incarnation) becomes its own named process row on ONE shared
  wall-clock axis, so a supervised multi-process run that crashed and
  relaunched shows both incarnations of every rank with the relaunch
  gap visible between them;
* a text summary — per-phase time share per (process, incarnation),
  per-request flow-point counts, one ``STALL`` row per lap that ran long
  (``stall`` spans, train/trace.py "Laps and stalls": loop, lap, wall,
  excess, where it stood, the cause and the OS readings behind it; on the
  timeline each lies on a ``stalls`` track of its own), the DROPPED-span
  count from each
  bounded tracer's footer (a truncated track is flagged TRUNCATED
  instead of reading as a quiet tail), and the compile ledger rollup
  (compiles, recompiles, total compile seconds, what changed).

Flow records (``kind="flow"``, train/trace.py ``Tracer.flow``) become
Chrome ``s``/``t``/``f`` flow events bound to the enclosing phase
slices, so Perfetto draws one request's admit -> prefill -> decode ->
retire arrows across the scheduler's tick spans.

Zero dependencies beyond the stdlib (proven under ``python -S`` like
``ckpt_fsck``) — usable on a host with no JAX to triage a trace dir
copied off a pod::

    python tools/trace_report.py TRACE_DIR                 # summary
    python tools/trace_report.py TRACE_DIR --out trace.json
    python tools/trace_report.py TRACE_DIR --json          # machine form
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import pathlib
import sys
from typing import Any, Dict, List, Optional, Tuple

Key = Tuple[str, int, int]  # (run_id, process_id, incarnation)

_JSONL_PY = (pathlib.Path(__file__).resolve().parent.parent
             / "neural_networks_parallel_training_with_mpi_tpu"
             / "utils" / "jsonl.py")


def _load_jsonl_mod():
    spec = importlib.util.spec_from_file_location("_nnpt_jsonl",
                                                  _JSONL_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


jz = _load_jsonl_mod()


def load_dir(dirpath: str) -> Dict[str, Any]:
    """All span + compile + autopilot-decision records under a trace
    dir, keyed by kind, plus the torn-line skip count from the shared
    tolerant reader."""
    spans: List[Dict[str, Any]] = []
    compiles: List[Dict[str, Any]] = []
    metas: List[Dict[str, Any]] = []
    skipped = 0
    for path in sorted(glob.glob(os.path.join(dirpath, "trace-*.jsonl"))):
        recs, skip = jz.read_jsonl(path)
        skipped += skip
        for rec in recs:
            kind = rec.get("kind")
            if kind in ("span", "instant", "flow"):
                spans.append(rec)
            elif kind == "meta":
                metas.append(rec)
    for path in sorted(glob.glob(os.path.join(dirpath,
                                              "compiles-*.jsonl"))):
        recs, skip = jz.read_jsonl(path)
        skipped += skip
        compiles.extend(r for r in recs if r.get("kind") == "compile")
    # the autopilot flight recorder (serve/autopilot.py events_path):
    # each decision becomes an instant event on its writer's track, so
    # Perfetto shows WHEN the control loop acted between the tick spans
    n_decisions = 0
    for path in sorted(glob.glob(os.path.join(dirpath,
                                              "autopilot*.jsonl"))):
        recs, skip = jz.read_jsonl(path)
        skipped += skip
        for rec in recs:
            if rec.get("kind") != "autopilot" or "t_unix" not in rec:
                continue
            n_decisions += 1
            inst = {"kind": "instant",
                    "name": f"autopilot:{rec.get('action', '?')}",
                    "t": rec.get("t_unix"),
                    "p": rec.get("p", 0), "run": rec.get("run", ""),
                    "inc": rec.get("inc", 0)}
            inst.update({k: v for k, v in rec.items()
                         if k not in ("kind", "t", "t_unix", "action",
                                      "p", "run", "inc")})
            spans.append(inst)
    return {"spans": spans, "compiles": compiles, "metas": metas,
            "autopilot_decisions": n_decisions,
            "lines_skipped": skipped}


def _key(rec: Dict[str, Any]) -> Key:
    return (str(rec.get("run", "")), int(rec.get("p", 0)),
            int(rec.get("inc", 0)))


def _groups(records: List[Dict[str, Any]]
            ) -> Dict[Key, List[Dict[str, Any]]]:
    out: Dict[Key, List[Dict[str, Any]]] = {}
    for r in records:
        out.setdefault(_key(r), []).append(r)
    return out


_META_KEYS = ("kind", "name", "t", "dur", "p", "run", "inc", "thread",
              "id", "fph")


# what a ``stall`` span says of its lap (train/trace.py "Laps and stalls"),
# in the order the stalls table prints it
_STALL_KEYS = ("loop", "n", "excess_s", "where", "where_s", "cause", "cpu_s",
               "cpu_other_s", "run_delay_s", "nvcsw", "nivcsw", "majflt",
               "gc_s", "trace_write_s", "compiles", "loadavg", "psi_cpu",
               "psi_io", "psi_mem")


def _is_stall(rec: Dict[str, Any]) -> bool:
    return rec.get("kind") == "span" and rec.get("name") == "stall"


def to_chrome(data: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Chrome trace-event JSON: one Chrome 'process' per (run, process,
    incarnation) group, named so Perfetto's track labels carry the
    correlation triple; ts normalized to the earliest record so the
    numbers stay readable (relative microseconds on one shared axis)."""
    spans = data["spans"]
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(float(r["t"]) for r in spans if "t" in r)
    events: List[Dict[str, Any]] = []
    tids: Dict[Tuple[Key, str], int] = {}
    for cpid, (key, recs) in enumerate(sorted(_groups(spans).items())):
        run, p, inc = key
        events.append({"ph": "M", "name": "process_name", "pid": cpid,
                       "tid": 0,
                       "args": {"name": f"proc {p} / incarnation {inc}"
                                        f" [{run}]"}})
        for r in recs:
            # a stall lies over its whole lap's spans: a track of its own
            thread = ("stalls" if _is_stall(r) else r.get("thread", "main"))
            tkey = (key, thread)
            if tkey not in tids:
                tids[tkey] = sum(1 for (k, _t) in tids if k == key)
            tid = tids[tkey]
            args = {k: v for k, v in r.items() if k not in _META_KEYS}
            ev = {"name": r.get("name", "?"), "pid": cpid, "tid": tid,
                  "ts": round((float(r.get("t", t0)) - t0) * 1e6, 1)}
            if r.get("kind") == "instant":
                ev.update(ph="i", s="p")
            elif r.get("kind") == "flow":
                # Chrome flow events (s/t/f): Perfetto binds each point
                # to the slice enclosing its ts on this track and draws
                # the arrows — one request's admit -> prefill chunks ->
                # first decode tick -> retire path across the tick spans
                # (train/trace.py Tracer.flow; the id carries the
                # process prefix, so merged fleet flows never collide)
                ev.update(ph=str(r.get("fph", "t")), cat="flow",
                          id=str(r.get("id", "?")))
                if ev["ph"] == "f":
                    ev["bp"] = "e"  # bind the finish to the enclosing slice
            else:
                ev.update(ph="X",
                          dur=round(float(r.get("dur", 0.0)) * 1e6, 1))
            if args:
                ev["args"] = args
            events.append(ev)
        for (key2, thread), tid in sorted(tids.items()):
            if key2 == key and thread != "main":
                events.append({"ph": "M", "name": "thread_name",
                               "pid": cpid, "tid": tid,
                               "args": {"name": thread}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def summarize(data: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Machine-readable rollup: per-(process, incarnation) phase time
    share + span counts, run ids seen, relaunch gaps, and the compile
    ledger totals per incarnation."""
    spans = [r for r in data["spans"] if r.get("kind") == "span"]
    flows = [r for r in data["spans"] if r.get("kind") == "flow"]
    out: Dict[str, Any] = {"runs": sorted({_key(r)[0] for r in spans}),
                           "groups": [], "compiles": [],
                           "autopilot_decisions":
                               data.get("autopilot_decisions", 0),
                           "lines_skipped": data.get("lines_skipped", 0)}
    # the bounded-trace footer: each tracer's final meta record counts
    # the spans dropped past the event cap.  Surfacing it per track is
    # what keeps a truncated timeline from reading as a complete one —
    # a 100k-event serving run that dropped 40k spans LOOKS quiet at
    # the end, and only this counter says otherwise.
    dropped: Dict[Key, int] = {}
    for m in data["metas"]:
        if m.get("final"):
            d = int(m.get("dropped", 0) or 0)
            key = _key(m)
            dropped[key] = max(dropped.get(key, 0), d)
    out["dropped_spans_total"] = sum(dropped.values())
    flow_groups = _groups(flows)
    groups = _groups(spans)
    for key in sorted(set(groups) | set(flow_groups) | set(dropped)):
        run, p, inc = key
        # a stall repeats its lap's seconds: listed apart, not a phase
        stalls = [r for r in groups.get(key, []) if _is_stall(r)]
        recs = [r for r in groups.get(key, []) if not _is_stall(r)]
        starts = [float(r["t"]) for r in recs]
        ends = [float(r["t"]) + float(r.get("dur", 0.0)) for r in recs]
        wall = max(ends) - min(starts) if recs else 0.0
        phases: Dict[str, Dict[str, float]] = {}
        for r in recs:
            ph = phases.setdefault(str(r.get("name", "?")),
                                   {"count": 0, "total_s": 0.0})
            ph["count"] += 1
            ph["total_s"] += float(r.get("dur", 0.0))
        for ph in phases.values():
            ph["total_s"] = round(ph["total_s"], 6)
            ph["share"] = (round(min(1.0, ph["total_s"] / wall), 4)
                           if wall else None)
        out["groups"].append({
            "run": run, "process": p, "incarnation": inc,
            "n_spans": len(recs),
            "n_flows": len(flow_groups.get(key, [])),
            "dropped_spans": dropped.get(key, 0),
            "t_first": round(min(starts), 6) if starts else None,
            "t_last": round(max(ends), 6) if ends else None,
            "wall_s": round(wall, 6),
            "phases": phases,
            "stalls": [{"t": r.get("t"), "wall_s": r.get("dur"),
                        **{k: r.get(k) for k in _STALL_KEYS}}
                       for r in stalls],
        })
    # relaunch gaps: for each (run, process), the quiet time between one
    # incarnation's last span and the next incarnation's first
    by_proc: Dict[Tuple[str, int], List[Dict[str, Any]]] = {}
    for g in out["groups"]:
        by_proc.setdefault((g["run"], g["process"]), []).append(g)
    gaps = []
    for (run, p), gs in sorted(by_proc.items()):
        gs = sorted(gs, key=lambda g: g["incarnation"])
        for a, b in zip(gs, gs[1:]):
            if a["t_last"] is not None and b["t_first"] is not None:
                gaps.append({"run": run, "process": p,
                             "from_incarnation": a["incarnation"],
                             "to_incarnation": b["incarnation"],
                             "gap_s": round(b["t_first"] - a["t_last"],
                                            6)})
    out["relaunch_gaps"] = gaps
    for key, recs in sorted(_groups(data["compiles"]).items()):
        run, p, inc = key
        recompiles = [r for r in recs
                      if r.get("changed") or r.get("added")
                      or r.get("removed")]
        out["compiles"].append({
            "run": run, "process": p, "incarnation": inc,
            "n_compiles": len(recs),
            "compile_s": round(sum((r.get("compile_ms") or 0.0)
                                   for r in recs) / 1e3, 3),
            "lower_s": round(sum((r.get("lower_ms") or 0.0)
                                 for r in recs) / 1e3, 3),
            "by_name": {
                name: len([r for r in recs if r.get("name") == name])
                for name in sorted({str(r.get("name")) for r in recs})},
            "recompiles": [
                {"name": r.get("name"), "n_compile": r.get("n_compile"),
                 **{k: r[k] for k in ("changed", "added", "removed")
                    if r.get(k)}}
                for r in recompiles],
        })
    return out


def _stall_row(st: Dict[str, Any]) -> str:
    def num(key, fmt=".2f"):
        return "n/a" if st.get(key) is None else format(st[key], fmt)

    row = (f"  STALL {st.get('loop')} {st.get('n')}: {num('wall_s')}s wall, "
           f"{num('excess_s')}s over the median, in {st.get('where')} "
           f"{num('where_s')}s, cause {st.get('cause')}; cpu {num('cpu_s')}s"
           f", other threads {num('cpu_other_s')}s, run delay "
           f"{num('run_delay_s')}s, switches {st.get('nvcsw')} voluntary / "
           f"{st.get('nivcsw')} involuntary, {st.get('majflt')} major "
           f"faults, gc {num('gc_s')}s, trace write {num('trace_write_s')}s"
           f", {st.get('compiles')} compile(s)")
    psi = ", ".join(f"{label} {st[key]:g}%" for key, label in
                    (("psi_cpu", "cpu"), ("psi_io", "io"),
                     ("psi_mem", "memory")) if st.get(key) is not None)
    if psi:
        row += f"; pressure {psi}"
    if st.get("loadavg") is not None:
        row += f"; load {st['loadavg']:g}"
    return row


def render_text(summary: Dict[str, Any]) -> str:
    lines: List[str] = []
    runs = summary.get("runs", [])
    lines.append(f"runs: {', '.join(runs) if runs else '(none)'}")
    for g in summary["groups"]:
        flows = (f" (+{g['n_flows']} flow points)"
                 if g.get("n_flows") else "")
        lines.append(f"proc {g['process']} / incarnation "
                     f"{g['incarnation']}: {g['n_spans']} spans over "
                     f"{g['wall_s']:.3f}s wall{flows}")
        if g.get("dropped_spans"):
            lines.append(f"  TRUNCATED: {g['dropped_spans']} span(s) "
                         "dropped past the event cap — this track's "
                         "tail is missing, not quiet")
        phases = sorted(g["phases"].items(),
                        key=lambda kv: -kv[1]["total_s"])
        for name, ph in phases:
            share = ("" if ph["share"] is None
                     else f"  {100 * ph['share']:5.1f}%")
            lines.append(f"  {name:<16} {ph['count']:>6}x  "
                         f"{ph['total_s']:>10.3f}s{share}")
        for st in g.get("stalls", []):
            lines.append(_stall_row(st))
    for gap in summary.get("relaunch_gaps", []):
        lines.append(f"relaunch gap: proc {gap['process']} incarnation "
                     f"{gap['from_incarnation']} -> "
                     f"{gap['to_incarnation']}: {gap['gap_s']:.3f}s quiet")
    for c in summary.get("compiles", []):
        lines.append(f"compiles: proc {c['process']} / incarnation "
                     f"{c['incarnation']}: {c['n_compiles']} compile(s), "
                     f"{c['compile_s']:.2f}s compiling "
                     f"(+{c['lower_s']:.2f}s lowering)")
        for name, n in c["by_name"].items():
            lines.append(f"  {name:<40} x{n}")
        for r in c["recompiles"]:
            what = []
            for k in ("changed", "added", "removed"):
                if r.get(k):
                    what.append(f"{k}: "
                                + ", ".join(f"{p}"
                                            + (f" {v['from']} -> {v['to']}"
                                               if isinstance(v, dict)
                                               else f" {v}")
                                            for p, v in r[k].items()))
            lines.append(f"  RECOMPILE {r['name']} (#{r['n_compile']}): "
                         + ("; ".join(what) if what else "?"))
    if summary.get("autopilot_decisions"):
        lines.append(f"autopilot: {summary['autopilot_decisions']} "
                     "decision(s) drawn as instant events on their "
                     "writers' tracks")
    if summary.get("lines_skipped"):
        lines.append(f"note: {summary['lines_skipped']} unparseable "
                     "JSONL line(s) skipped (torn tail of a "
                     "live/killed writer)")
    if not summary["groups"]:
        lines.append("(no spans found)")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir", help="a --trace_dir (or the trace/ "
                                      "subdir of a --telemetry_dir)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the merged Chrome/Perfetto trace JSON "
                         "here (default: <trace_dir>/trace.json)")
    ap.add_argument("--no-chrome", action="store_true",
                    help="summary only; skip writing trace.json")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.trace_dir):
        print(f"ERROR: not a directory: {args.trace_dir}",
              file=sys.stderr)
        return 2
    data = load_dir(args.trace_dir)
    if not data["spans"] and not data["compiles"]:
        print(f"ERROR: no trace-*.jsonl / compiles-*.jsonl records "
              f"under {args.trace_dir}", file=sys.stderr)
        return 2
    summary = summarize(data)
    if not args.no_chrome:
        out = args.out or os.path.join(args.trace_dir, "trace.json")
        with open(out, "w") as f:
            json.dump(to_chrome(data), f)
        summary["chrome_trace"] = out
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(render_text(summary))
        if "chrome_trace" in summary:
            print(f"merged Perfetto trace -> {summary['chrome_trace']} "
                  "(open in https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
