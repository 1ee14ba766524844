#!/usr/bin/env python3
"""Where a traced benchmark run's device time went, by the program's names.

    JAX_PLATFORMS=cpu python tools/scope_report.py [benchmark/out]
    python tools/scope_report.py --workload <cell> --seed <n> --seconds <s>

The first form reads the newest ``*.xplane.pb`` under the run directory of
``benchmark/run.py --trace 1`` and prints one JSON object: busy time by leaf
``jax.named_scope`` and what no scope holds (``benchmark/reducers/scopes.py``
``coverage``), the ``attention`` scope's time by what implements it
(``attn_dense``, ``attn_flash``, ... and under ``attn_flash`` each Pallas
kernel's own), and idle time by the deepest ``nnpt:`` span over it
(``host_phases.py`` ``by_span``).  PERF.md section 5 is written from it.

The second form (on the chip) makes that traced run itself, through
``benchmark/run.py``'s own ``main`` (the cells list the scope and idle
metrics themselves), so it prints the run's result line, then the report.
"""

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# path components: what implements the attention, and the Pallas kernels'
# own names (``pl.pallas_call(name=...)``) under it
_IMPL = re.compile(r"(?:^|[/(])(attn_\w+?)(?=[)/]|$)")
_KERNEL = re.compile(r"(?:^|[/(])(flash_fwd|flash_bwd|paged_attention)"
                     r"(?=[)/]|$)")


class _Trace:
    def __init__(self, path):
        self.path = path

    def trace_file(self):
        return self.path


def attention_by_impl(obs) -> dict:
    """{``attn_<impl>``: {"total": s, <kernel>: s, ..., "around": s}} on the
    first chip: device self seconds under each implementation's scope, each
    Pallas kernel's own and what XLA does around them (layout changes,
    ``delta``); {} where the trace holds no such scope."""
    from benchmark.reducers import scopes

    trace = scopes.trace_of(obs)
    out = {}
    if not trace:
        return out
    attention = scopes.component("attention")
    for path, _start, own in trace[sorted(trace)[0]]["self"]:
        impl = _IMPL.findall(path) if attention.search(path) else None
        if not impl:
            continue
        kernel = _KERNEL.findall(path)
        row = out.setdefault(impl[-1], {"total": 0.0})
        part = kernel[-1] if kernel else "around"
        row["total"] += own / 1e9
        row[part] = row.get(part, 0.0) + own / 1e9
    return out


def main(argv) -> int:
    from benchmark.reducers import host_phases, scopes

    out_dir = ROOT / "benchmark" / "out"
    if "--workload" in argv:
        from benchmark import run as runner

        rc = runner.main([*argv[1:], "--trace", "1"])
        if rc:
            return rc
    elif len(argv) > 1:
        out_dir = Path(argv[1])
    traces = sorted(out_dir.glob("xplane/plugins/profile/*/*.xplane.pb"))
    if not traces:
        print(f"no trace under {out_dir}", file=sys.stderr)
        return 1
    obs = {"profiler": _Trace(traces[-1])}
    print(json.dumps({"trace": str(traces[-1]),
                      "coverage": scopes.coverage(obs, top=12),
                      "attention_by_impl_s": attention_by_impl(obs),
                      "idle_by_span_s": host_phases.by_span(obs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
