"""Render a telemetry run's health from its --telemetry_dir artifacts.

Reads the metrics JSONL (per-step grad/param norms, update ratio, loss,
mfu, step time — train.telemetry) plus heartbeat.json / postmortem.json
when present, and prints percentiles and trends::

    python tools/metrics_summary.py RUN_DIR            # a --telemetry_dir
    python tools/metrics_summary.py metrics.jsonl      # a bare JSONL
    python tools/metrics_summary.py RUN_DIR --last 200 # tail window only
    python tools/metrics_summary.py RUN_DIR --json     # machine-readable

Zero dependencies beyond the stdlib — usable on a host with no JAX, e.g.
to triage a run directory copied off a pod.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))
    return sorted_vals[i]


def load_records_counted(path: str, last: int = 0
                         ) -> "tuple[List[Dict[str, Any]], int]":
    """Tolerant JSONL load via the shared ``utils/jsonl`` reader:
    returns ``(records, skipped)`` where ``skipped`` counts torn/bad
    lines.  A missing file still raises OSError (callers distinguish
    'no file' from 'empty stream')."""
    with open(path):
        pass  # existence/permission check — the reader treats absence as empty
    records, skipped = _jsonl_mod().read_jsonl(path)
    return (records[-last:] if last > 0 else records), skipped


def load_records(path: str, last: int = 0) -> List[Dict[str, Any]]:
    return load_records_counted(path, last=last)[0]


def _series(records, key) -> List[float]:
    out = []
    for r in records:
        v = r.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out.append(float(v))
    return out


def _stat_row(name: str, vals: List[float], unit: str = "") -> Optional[str]:
    if not vals:
        return None
    s = sorted(vals)
    return (f"  {name:<14} p50 {_percentile(s, 0.50):.6g}   "
            f"p95 {_percentile(s, 0.95):.6g}   max {s[-1]:.6g}"
            + (f" {unit}" if unit else ""))


def summarize(records: List[Dict[str, Any]],
              windowed: bool = False) -> Dict[str, Any]:
    steps = _series(records, "step")
    losses = _series(records, "loss")
    out: Dict[str, Any] = {
        "n_records": len(records),
        "step_first": int(steps[0]) if steps else None,
        "step_last": int(steps[-1]) if steps else None,
    }
    if losses:
        out["loss_first"] = losses[0]
        out["loss_last"] = losses[-1]
        out["loss_min"] = min(losses)
        nonfinite = sum(1 for r in records
                        if isinstance(r.get("loss"), float)
                        and not math.isfinite(r["loss"]))
        out["nonfinite_losses"] = nonfinite
    for key in ("grad_norm", "param_norm", "update_ratio",
                "step_time_ms", "samples_per_sec", "mfu"):
        vals = sorted(_series(records, key))
        if vals:
            out[key] = {"p50": _percentile(vals, 0.50),
                        "p95": _percentile(vals, 0.95),
                        "max": vals[-1]}
    # 'skipped' is the guard's CUMULATIVE rejection counter per record
    # (train.telemetry) — total fires = sum of positive increments, which
    # also stays correct across a rollback's counter rewind.  With a
    # --last window, seed from the first visible value so fires BEFORE
    # the window are not attributed to it.
    skipped = _series(records, "skipped")
    total = 0
    prev = skipped[0] if (windowed and skipped) else 0.0
    for v in skipped:
        if v > prev:
            total += int(v - prev)
        prev = v
    out["skipped_updates"] = total
    # RL records (rl/runner.py writes kind="rl" through the shared
    # telemetry stream): the health numbers are the return trend (is the
    # policy learning?), the PPO diagnostics (entropy should anneal,
    # approx_kl should stay small), and env frames/s (the Anakin
    # throughput headline)
    rl_recs = [r for r in records if r.get("kind") == "rl"]
    if rl_recs:
        rl_out: Dict[str, Any] = {"updates": len(rl_recs)}
        rets = _series(rl_recs, "return_mean")
        if rets:
            ema = rets[0]
            for v in rets:
                ema = 0.9 * ema + 0.1 * v
            rl_out["return_first"] = rets[0]
            rl_out["return_last"] = rets[-1]
            rl_out["return_max"] = max(rets)
            rl_out["return_ema"] = ema
        for key, label in (("samples_per_sec", "env_frames_per_sec"),
                           ("entropy", "entropy"),
                           ("approx_kl", "approx_kl"),
                           ("value_loss", "value_loss")):
            vals = sorted(_series(rl_recs, key))
            if vals:
                rl_out[label] = {"p50": _percentile(vals, 0.50),
                                 "p95": _percentile(vals, 0.95),
                                 "max": vals[-1]}
        times = _series(rl_recs, "step_time_ms")
        if times:
            rl_out["updates_per_sec"] = {
                "p50": 1e3 / _percentile(sorted(times), 0.50),
                "max": 1e3 / min(times)}
        out["rl"] = rl_out
    # serving records (serve/scheduler.py): kind="serve_req" carries one
    # completed request's latency pair — percentiles across requests are
    # THE serving health numbers — and kind="serve" ticks carry the
    # queue/pool state + cumulative admission counters
    serve_reqs = [r for r in records if r.get("kind") == "serve_req"]
    if serve_reqs:
        serving: Dict[str, Any] = {"requests": len(serve_reqs)}
        for key in ("ttft_ms", "itl_ms", "total_ms"):
            vals = sorted(_series(serve_reqs, key))
            if vals:
                serving[key] = {"p50": _percentile(vals, 0.50),
                                "p99": _percentile(vals, 0.99),
                                "max": vals[-1]}
        serving["evictions"] = int(sum(_series(serve_reqs, "evictions")))
        serving["deadline_missed"] = sum(
            1 for r in serve_reqs if r.get("deadline_missed"))
        out["serving"] = serving
    serve_ticks = [r for r in records if r.get("kind") == "serve"]
    if serve_ticks:
        tick_stats: Dict[str, Any] = {}
        for key in ("queue_depth", "block_utilization", "tokens_per_sec"):
            vals = sorted(_series(serve_ticks, key))
            if vals:
                tick_stats[key] = {"p50": _percentile(vals, 0.50),
                                   "p95": _percentile(vals, 0.95),
                                   "max": vals[-1]}
        last = serve_ticks[-1]
        # attended/padded are CUMULATIVE counters (their running ratio
        # converges, so percentiles would be distribution theater): the
        # run's honest summary is the final ratio — same story for the
        # prefix-cache hit/fork/eviction counters
        for key in ("admitted", "rejected", "evicted", "completed",
                    "tokens_out", "attended_keys", "padded_keys",
                    "attended_ratio", "walked_keys_share", "full_keys",
                    "window_keys", "full_blocks_held",
                    "window_blocks_held", "ssm_state_updates",
                    "ssm_prefill_tokens", "prefix_hits",
                    "prefix_misses",
                    "prefix_hit_tokens", "prefix_hit_rate",
                    "shared_blocks", "cow_forks", "cache_evictions",
                    "blocks_saved", "cached_free_blocks",
                    "prefill_chunks", "prefill_heads",
                    "stalls", "stall_s"):
            if key in last:
                tick_stats[key] = last[key]
        out["serving_ticks"] = tick_stats
    # kind="step" records carry the step loop's cumulative stall counters
    # (train/trace.py LapWatch): the run's figure is the last record's
    step_recs = [r for r in records if r.get("kind") == "step"
                 and "stalls" in r]
    if step_recs:
        out["step_stalls"] = {"stalls": step_recs[-1]["stalls"],
                              "stall_s": step_recs[-1].get("stall_s")}
    # kind="alert" records (train.telemetry EMA z-score anomalies,
    # serve/scheduler.py SLO burn rate): count by name + the last few,
    # so a triage pass sees WHAT fired without grepping the stream
    alert_recs = [r for r in records if r.get("kind") == "alert"]
    if alert_recs:
        by_name: Dict[str, int] = {}
        for a in alert_recs:
            key = str(a.get("alert"))
            by_name[key] = by_name.get(key, 0) + 1
        out["alerts"] = {
            "n": len(alert_recs), "by_name": by_name,
            "last": [{k: a.get(k) for k in
                      ("alert", "role", "step", "value", "z",
                       "burn_rate", "rid") if a.get(k) is not None}
                     for a in alert_recs[-5:]]}
    # kind="rollup" sketch snapshots (utils/sketches.py, loaded by file
    # path like trace_report): the NEWEST per (role, run, p, inc) merge
    # into per-role percentiles — the same math tools/obs_agg.py runs
    # fleet-wide, composed here so --json callers get one document
    rollup_recs = [r for r in records if r.get("kind") == "rollup"]
    if rollup_recs:
        sketches_mod = _sketches_mod()
        latest: Dict[tuple, Dict[str, Any]] = {}
        for r in rollup_recs:
            latest[(str(r.get("role")), str(r.get("run", "")),
                    int(r.get("p", 0)), int(r.get("inc", 0)))] = r
        views: Dict[str, Dict[str, Any]] = {}
        for (role, _run, _p, _inc), r in sorted(latest.items()):
            view = views.setdefault(role, {"writers": 0, "docs": {},
                                           "counters": {}})
            view["writers"] += 1
            for name, doc in (r.get("sketches") or {}).items():
                view["docs"].setdefault(name, []).append(doc)
            for name, val in (r.get("counters") or {}).items():
                if isinstance(val, (int, float)):
                    view["counters"][name] = (
                        view["counters"].get(name, 0) + val)
        out["rollups"] = {}
        for role, view in views.items():
            out["rollups"][role] = {
                "writers": view["writers"],
                "counters": view["counters"],
                "sketches": {
                    name: sketches_mod.merge_sketch_dicts(docs).summary(
                        (0.5, 0.9, 0.99))
                    for name, docs in sorted(view["docs"].items())}}
    # elastic topology-change events (kind=topology, train.telemetry):
    # the moments the run resumed on a different world than the one that
    # saved its checkpoint — effective batch/accumulation may change there
    out["topology_changes"] = [
        {"step": r.get("step"),
         "from_devices": (r.get("from_world") or {}).get("n_devices"),
         "to_devices": (r.get("to_world") or {}).get("n_devices"),
         "from_dp": (r.get("from_world") or {}).get("dp"),
         "to_dp": (r.get("to_world") or {}).get("dp"),
         "policy": r.get("policy"),
         "batch_size": r.get("batch_size"),
         "accum_steps": r.get("accum_steps")}
        for r in records if r.get("kind") == "topology"]
    return out


def _stalls_line(counters: Dict[str, Any], what: str) -> str:
    return (f"  STALLS: {counters['stalls']} {what} ran long, "
            f"{counters.get('stall_s') or 0.0:.3f} s over the median "
            "(the trace's `stall` spans say where: "
            "tools/trace_report.py)")


def serving_lines(summary: Dict[str, Any]) -> List[str]:
    """The serving view: request-latency percentiles + tick/pool/prefix-
    cache state — shared by the full render and ``--serve``."""
    lines: List[str] = []
    if "serving" in summary:
        sv = summary["serving"]
        lines.append(f"serving: {sv['requests']} requests")
        for key, label in (("ttft_ms", "ttft"), ("itl_ms", "itl"),
                           ("total_ms", "total")):
            if key in sv:
                lines.append(
                    f"  {label:<14} p50 {sv[key]['p50']:.6g}   "
                    f"p99 {sv[key]['p99']:.6g}   max {sv[key]['max']:.6g}"
                    " ms")
        if sv.get("evictions"):
            lines.append(f"  evictions: {sv['evictions']}")
        if sv.get("deadline_missed"):
            lines.append(f"  DEADLINES MISSED: {sv['deadline_missed']}")
    if "serving_ticks" in summary:
        st = summary["serving_ticks"]
        counters = "/".join(str(st.get(k, "?")) for k in
                            ("admitted", "rejected", "evicted",
                             "completed"))
        lines.append(f"serving ticks: adm/rej/evict/done {counters}, "
                     f"{st.get('tokens_out', 0)} tokens out")
        if st.get("attended_ratio") is not None:
            lines.append(
                f"  attended keys: {st.get('attended_keys')} / "
                f"{st.get('padded_keys')} padded "
                f"({st['attended_ratio']:.3f} "
                "— the fused kernel's skipped work)")
        if st.get("stalls"):
            lines.append(_stalls_line(st, "ticks"))
        if st.get("walked_keys_share") is not None:
            lines.append(
                f"  walked keys share: {st['walked_keys_share']:.3f} of "
                "the padded width (1.0 = gathered; below it the paged "
                "kernel ran)")
        if "window_keys" in st:
            lines.append(
                f"  keys read by kind of layer: {st.get('full_keys')} full"
                f" / {st['window_keys']} window; blocks held "
                f"{st.get('full_blocks_held')} / "
                f"{st.get('window_blocks_held')} (decode ticks, summed "
                "over layers)")
        if "ssm_state_updates" in st:
            lines.append(
                f"  recurrent state: {st['ssm_state_updates']} rows updated "
                f"by decode ticks, {st.get('ssm_prefill_tokens')} prompt "
                "columns through the chunked recurrence (each summed over "
                "the mixer layers)")
        if st.get("prefill_chunks"):
            chunks, heads = st["prefill_chunks"], st.get("prefill_heads", 0)
            lines.append(
                f"  prefill chunks: {chunks}, the head ran in {heads} (a "
                f"prompt's last chunk; {heads / chunks:.3f})")
        if "prefix_hits" in st:
            rate = st.get("prefix_hit_rate")
            lines.append(
                f"  prefix cache: hit rate "
                f"{'?' if rate is None else format(rate, '.3f')} "
                f"({st.get('prefix_hits')} hits / "
                f"{st.get('prefix_misses')} misses, "
                f"{st.get('prefix_hit_tokens')} prompt tokens from "
                "cache)")
            lines.append(
                f"  shared blocks {st.get('shared_blocks')} now / "
                f"{st.get('blocks_saved')} saved total, "
                f"CoW forks {st.get('cow_forks')}, "
                f"cache evictions {st.get('cache_evictions')}, "
                f"{st.get('cached_free_blocks')} cached-free")
        for key, unit in (("queue_depth", ""),
                          ("block_utilization", ""),
                          ("tokens_per_sec", "tok/s")):
            if key in st:
                lines.append(
                    f"  {key:<18} p50 {st[key]['p50']:.6g}   "
                    f"p95 {st[key]['p95']:.6g}   max {st[key]['max']:.6g}"
                    + (f" {unit}" if unit else ""))
    return lines


def render_text(summary: Dict[str, Any], records: List[Dict[str, Any]],
                heartbeat: Optional[Dict[str, Any]],
                heartbeat_age: Optional[float],
                postmortem: Optional[Dict[str, Any]]) -> str:
    lines = [f"records: {summary['n_records']} "
             f"(steps {summary.get('step_first')} -> "
             f"{summary.get('step_last')})"]
    if "loss_last" in summary:
        lines.append(f"  loss           {summary['loss_first']:.6g} -> "
                     f"{summary['loss_last']:.6g} "
                     f"(min {summary['loss_min']:.6g})")
        if summary.get("nonfinite_losses"):
            lines.append(f"  NON-FINITE losses: "
                         f"{summary['nonfinite_losses']}")
    for key, unit in (("grad_norm", ""), ("param_norm", ""),
                      ("update_ratio", ""), ("step_time_ms", "ms"),
                      ("samples_per_sec", "samples/s"), ("mfu", "")):
        row = _stat_row(key, _series(records, key), unit)
        if row:
            lines.append(row)
    if summary.get("skipped_updates"):
        lines.append(f"  skipped updates: {summary['skipped_updates']} "
                     "(guarded steps rejected — see postmortem/events)")
    if (summary.get("step_stalls") or {}).get("stalls"):
        lines.append(_stalls_line(summary["step_stalls"], "steps"))
    for t in summary.get("topology_changes", []):
        bs = t.get("batch_size") or [None, None]
        ac = t.get("accum_steps") or [None, None]
        detail = []
        if bs[0] != bs[1]:
            detail.append(f"batch {bs[0]} -> {bs[1]}")
        if ac[0] != ac[1]:
            detail.append(f"accum {ac[0]} -> {ac[1]}")
        lines.append(
            f"topology: {t.get('from_devices')} -> {t.get('to_devices')} "
            f"devices (dp {t.get('from_dp')} -> {t.get('to_dp')}) at step "
            f"{t.get('step')}, policy {t.get('policy')}"
            + (f" ({', '.join(detail)})" if detail else ""))
    if "rl" in summary:
        rl = summary["rl"]
        rl_recs = [r for r in records if r.get("kind") == "rl"]
        lines.append(f"rl: {rl['updates']} updates")
        if "return_last" in rl:
            lines.append(
                f"  return         {rl['return_first']:.6g} -> "
                f"{rl['return_last']:.6g} (EMA {rl['return_ema']:.6g}, "
                f"max {rl['return_max']:.6g})")
        for key, label, unit in (
                ("samples_per_sec", "env_frames/s", "frames/s"),
                ("entropy", "entropy", ""),
                ("approx_kl", "approx_kl", ""),
                ("value_loss", "value_loss", "")):
            row = _stat_row(label, _series(rl_recs, key), unit)
            if row:
                lines.append(row)
        if "updates_per_sec" in rl:
            lines.append(
                f"  updates/s      p50 {rl['updates_per_sec']['p50']:.6g}"
                f"   max {rl['updates_per_sec']['max']:.6g}")
    if "alerts" in summary:
        al = summary["alerts"]
        lines.append(f"ALERTS: {al['n']} (" + ", ".join(
            f"{k} x{v}" for k, v in al["by_name"].items()) + ")")
        for a in al["last"]:
            detail = a.get("burn_rate") or a.get("z") or a.get("value")
            lines.append(f"  {a.get('alert')} @ step {a.get('step')}"
                         + (f" = {detail}" if detail is not None else ""))
    for role, view in (summary.get("rollups") or {}).items():
        lines.append(f"rollups [{role}]: {view['writers']} writer(s)")
        for name, s in view["sketches"].items():
            if s.get("p50") is None:
                continue
            lines.append(
                f"  {name:<18} p50 {s['p50']:.6g}   p90 {s['p90']:.6g}"
                f"   p99 {s['p99']:.6g}   (n={s['n']}, "
                f"±{s['rank_error_bound'] * 100:.1f}% rank)")
    lines += serving_lines(summary)
    if heartbeat is not None:
        age = ("?" if heartbeat_age is None
               else f"{heartbeat_age:.1f}s ago")
        rate = heartbeat.get("steps_per_sec_ema")
        lines.append(f"heartbeat: step {heartbeat.get('step')} ({age})"
                     + (f", {rate:.2f} steps/s EMA" if rate else "")
                     + (" [FINAL]" if heartbeat.get("final") else ""))
    if postmortem is not None:
        lines.append(f"postmortem: {postmortem.get('reason')!r} with "
                     f"{postmortem.get('n_records')} records "
                     f"at {postmortem.get('written_iso')}")
        events = [r for r in postmortem.get("records", [])
                  if r.get("kind") == "event"]
        for e in events[-5:]:
            lines.append(f"  event: {e.get('event')} @ step "
                         f"{e.get('step')}")
    if summary.get("lines_skipped"):
        lines.append(f"note: {summary['lines_skipped']} unparseable "
                     "JSONL line(s) skipped (torn tail of a "
                     "live/killed writer)")
    return "\n".join(lines)


def _trace_report_mod():
    """tools/trace_report.py loaded by file path (works as a script, as
    a module, and under ``python -S``) — the trace view reuses its
    loader/summary instead of duplicating the merge semantics."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "trace_report.py")
    spec = importlib.util.spec_from_file_location("_nnpt_trace_report",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_jsonl_cache = None


def _jsonl_mod():
    """utils/jsonl.py — the one tolerant JSONL reader every
    observability tool shares — loaded by file path so it works as a
    bare script under ``python -S``."""
    global _jsonl_cache
    if _jsonl_cache is None:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "neural_networks_parallel_training_with_mpi_tpu", "utils",
            "jsonl.py")
        spec = importlib.util.spec_from_file_location("_nnpt_jsonl",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _jsonl_cache = mod
    return _jsonl_cache


_sketches_cache = None


def _sketches_mod():
    """utils/sketches.py loaded by file path (the ckpt_fsck convention,
    shared with tools/obs_agg.py) — merging rollup snapshots must work
    on a jax-less host under ``python -S``."""
    global _sketches_cache
    if _sketches_cache is None:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "neural_networks_parallel_training_with_mpi_tpu", "utils",
            "sketches.py")
        spec = importlib.util.spec_from_file_location("_nnpt_sketches",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _sketches_cache = mod
    return _sketches_cache


def trace_view(path: str) -> Optional[Dict[str, Any]]:
    """The --trace summary for a run dir: looks for the span/ledger
    files in ``path/trace`` (the --telemetry_dir layout) falling back to
    ``path`` itself (an explicit --trace_dir).  Returns the
    trace_report summary dict, or None when no trace exists."""
    tr = _trace_report_mod()
    for cand in (os.path.join(path, "trace"), path):
        if os.path.isdir(cand):
            data = tr.load_dir(cand)
            if data["spans"] or data["compiles"]:
                summary = tr.summarize(data)
                summary["trace_dir"] = cand
                summary["_render"] = tr.render_text(summary)
                return summary
    return None


def autopilot_view(path: str) -> Optional[Dict[str, Any]]:
    """The --autopilot summary: every ``kind="autopilot"`` decision from
    the ledger files (``autopilot*.jsonl`` in the run dir or its
    ``trace/`` subdir) — count by action plus the recent tail."""
    import glob as glob_lib

    paths: List[str] = []
    for cand in (path, os.path.join(path, "trace")):
        if os.path.isdir(cand):
            paths.extend(sorted(glob_lib.glob(
                os.path.join(cand, "autopilot*.jsonl"))))
    if not os.path.isdir(path) and os.path.isfile(path):
        paths.append(path)  # an explicit ledger file
    recs, skipped = _jsonl_mod().read_many(paths)
    decisions = [r for r in recs
                 if r.get("kind") == "autopilot" or "action" in r]
    if not decisions:
        return None
    by_action: Dict[str, int] = {}
    for d in decisions:
        key = str(d.get("action"))
        by_action[key] = by_action.get(key, 0) + 1
    return {"n": len(decisions), "by_action": by_action,
            "lines_skipped": skipped, "last": decisions[-10:]}


_AUTOPILOT_META = ("kind", "t", "t_unix", "action", "run", "p", "inc")


def autopilot_lines(view: Dict[str, Any]) -> List[str]:
    lines = [f"autopilot: {view['n']} decision(s) (" + ", ".join(
        f"{k} x{v}" for k, v in sorted(view["by_action"].items()))
        + ")"]
    for d in view["last"]:
        extra = ", ".join(f"{k}={v}" for k, v in d.items()
                          if k not in _AUTOPILOT_META)
        lines.append(f"  t+{d.get('t', '?')}s {d.get('action')}"
                     + (f"  ({extra})" if extra else ""))
    if view.get("lines_skipped"):
        lines.append(f"  note: {view['lines_skipped']} unparseable "
                     "ledger line(s) skipped")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="a --telemetry_dir or a metrics JSONL file")
    ap.add_argument("--last", type=int, default=0,
                    help="summarize only the last N records")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object")
    ap.add_argument("--trace", action="store_true",
                    help="also summarize the run's span trace + compile "
                         "ledger (trace/ subdir or an explicit trace "
                         "dir): per-phase time share and compile "
                         "count/seconds per incarnation")
    ap.add_argument("--serve", action="store_true",
                    help="serving-only view: TTFT/ITL percentiles, tick "
                         "counters, attended-keys ratio, and the prefix-"
                         "cache columns (hit rate, shared blocks, CoW "
                         "forks, blocks saved) — nothing from the "
                         "training stream")
    ap.add_argument("--autopilot", action="store_true",
                    help="autopilot-decision view: the persisted "
                         "control-loop ledger (autopilot*.jsonl) — "
                         "decision counts by action and the recent "
                         "tail")
    args = ap.parse_args(argv)

    heartbeat = postmortem = None
    heartbeat_age = None
    heartbeats = []
    if os.path.isdir(args.path):
        import glob as glob_lib

        metrics_path = os.path.join(args.path, "metrics.jsonl")
        pm_path = os.path.join(args.path, "postmortem.json")
        # every heartbeat in the dir: the legacy shared heartbeat.json
        # and/or the per-role heartbeat-<role>-p<P>.json forms (two
        # programs sharing one dir each own a file now); the FRESHEST
        # one keeps the single-heartbeat render/json shape
        for p in sorted(glob_lib.glob(
                os.path.join(args.path, "heartbeat*.json"))):
            try:
                with open(p) as f:
                    doc = json.load(f)
                age = max(0.0, time.time() - os.stat(p).st_mtime)
            except (OSError, ValueError):
                continue
            heartbeats.append({"file": os.path.basename(p),
                               "age_s": round(age, 3), **doc})
            if heartbeat_age is None or age < heartbeat_age:
                heartbeat, heartbeat_age = doc, age
        try:
            with open(pm_path) as f:
                postmortem = json.load(f)
        except (OSError, ValueError):
            pass
    else:
        metrics_path = args.path
    lines_skipped = 0
    try:
        records, lines_skipped = load_records_counted(metrics_path,
                                                      last=args.last)
    except OSError as e:
        if not (args.trace or args.autopilot):
            print(f"ERROR: cannot read {metrics_path}: {e}",
                  file=sys.stderr)
            return 2
        records = []  # trace/ledger-only view, no metrics stream
    summary = summarize(records, windowed=args.last > 0)
    if lines_skipped:
        summary["lines_skipped"] = lines_skipped
    trace = trace_view(args.path) if args.trace else None
    pilot = autopilot_view(args.path) if args.autopilot else None
    if args.json:
        if args.serve:
            summary = {k: v for k, v in summary.items()
                       if k in ("n_records", "serving", "serving_ticks")}
        if args.autopilot:
            summary["autopilot"] = pilot
        summary["heartbeat"] = heartbeat
        summary["heartbeat_age_s"] = heartbeat_age
        if len(heartbeats) > 1:
            summary["heartbeats"] = heartbeats
        summary["postmortem_reason"] = (postmortem or {}).get("reason")
        if trace is not None:
            trace.pop("_render", None)
            summary["trace"] = trace
        print(json.dumps(summary, indent=2))
    elif args.autopilot:
        print("\n".join(autopilot_lines(pilot)) if pilot
              else "no autopilot decisions (autopilot*.jsonl) found")
    elif args.serve:
        out = serving_lines(summary)
        print("\n".join(out) if out
              else "no serving records (kind=serve/serve_req) found")
    else:
        print(render_text(summary, records, heartbeat, heartbeat_age,
                          postmortem))
        if args.trace:
            if trace is None:
                print("trace: no span/ledger files found")
            else:
                print(f"trace ({trace['trace_dir']}):")
                print(trace["_render"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
