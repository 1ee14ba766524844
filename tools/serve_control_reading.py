#!/usr/bin/env python3
"""Read the two numbers a serving cell's ``served_gap_mean_sigma`` limit is
set between, at the cell's own size on the chip.

    chiprun --chips 1 -- python tools/serve_control_reading.py \\
        --workload <cell> --seeds 1,2,3 --seconds 10

For each seed: the cell through the unmodified harness (a short window), then
the float32 reference and the CONTROL over the requests the run sampled: the
reference computed with fp8 e4m3 operands (``benchmark/reference/control.py``),
reading how far below the float32 reference's best the token lies that the
control puts first.  Prints one JSON line a seed: the program's reading (must
lie under the limit) and the control's (must lie over it).  PERF.md section 2
says how a limit is placed between them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import check, common, serve_closed_loop
    from benchmark.reference import control
    from benchmark.reference import serve as ref_serve

    cell = common.load_cell(args.workload)
    try:
        dev = common.bring_up(cell["chips"])
    except common.NoAccelerator as e:
        print(f"serve_control_reading: {e}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        res = serve_closed_loop.run(cell, seed, args.seconds, False, dev,
                                    time.perf_counter())
        seqs = [toks for _p, toks in res["obs"]["sample"]]
        plens = [p for p, _toks in res["obs"]["sample"]]
        ref, _ = ref_serve.generated_logits(cell["model"], seed, seqs, plens)
        low, _ = ref_serve.generated_logits(cell["model"], seed, seqs, plens,
                                            quant=control.fp8_cast)
        gaps = check.served_gap(ref, jax.device_get(low.argmax(-1)))
        del ref, low
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_gap_mean_sigma": float(res["obs"]["gaps"].mean()),
            "control_fp8_gap_mean_sigma": float(gaps.mean()),
            "tokens": int(len(gaps)), "program_correct": res["correct"],
            "limit": cell["limits"]["served_gap_mean_sigma"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
