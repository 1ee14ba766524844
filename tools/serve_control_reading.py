#!/usr/bin/env python3
"""Read the two numbers a serving cell's ``served_gap_mean_sigma`` limit is
set between, at the cell's own size on the chip.

    chiprun --chips 1 -- python tools/serve_control_reading.py \\
        --workload <cell> --seeds 1,2,3 --seconds 10 [--controls fp8,...]

For each seed: the cell through the unmodified harness (a short window), then
the float32 reference and each CONTROL over the requests the run sampled,
reading how far below the float32 reference's best the token lies that the
control puts first.  The controls (``--controls``, fp8 alone by default):

* ``fp8``: the reference computed with fp8 e4m3 operands in every projection
  (``benchmark/reference/control.py``), the nearest precision below a
  bfloat16 configuration's;
* ``state_bf16``: the reference with its recurrent state rounded to bfloat16
  once a token (a family that reads ``gdn_state_dtype``: ``olmo_hybrid``);
* ``bf16``: the reference computed with bfloat16 operands in every
  projection, the configuration's own precision: not a control that has to
  fail, but what rounding the activations as the program does costs alone;
* ``bf16_hidden``: as ``bf16``, and the hidden state rounded to bfloat16
  where each layer hands it on, as the program keeps it.

Prints one JSON line a seed: the program's reading (must lie under the
limit) and each control's (``fp8`` and ``state_bf16`` must lie over it), and
beside each gap how many tokens differ from the reference's best
(``*_flips``) and how far a control's logits lie from the reference's in
standard deviations of a position's logits (``*_logit_sigma``, the mean
over positions of the root mean square difference): a control that moves
the logits too little to change a token reads a gap of 0, and this says by
how much it fell short.
PERF.md section 2 says how a limit is placed between them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


class HiddenInBf16:
    """A family as it is, but for the hidden state that ``embed`` and each
    ``block`` hand on, rounded by ``round_``."""

    def __init__(self, family, round_):
        self._family, self._round = family, round_

    def __getattr__(self, name):
        return getattr(self._family, name)

    def embed(self, model, outer, ids):
        return self._round(self._family.embed(model, outer, ids))

    def block(self, model, p, x, i, quant=None):
        return self._round(self._family.block(model, p, x, i, quant))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="fp8",
                    help="comma-separated, of fp8, state_bf16, bf16, "
                         "bf16_hidden")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import check, common, serve_closed_loop
    from benchmark.reference import control
    from benchmark.reference import serve as ref_serve

    cell = common.load_cell(args.workload)
    model = cell["model"]
    # reduce_precision and not a cast to bfloat16 and back: a compiler may
    # drop such a pair as excess precision (the chip's drops the hidden
    # state's), and the rounding is the point
    bf16 = lambda x: jax.lax.reduce_precision(x, 8, 7)  # noqa: E731
    controls = {"fp8": (model, control.fp8_cast),
                "state_bf16": ({**model, "gdn_state_dtype": "bfloat16"},
                               None),
                "bf16": (model, bf16),
                "bf16_hidden": ({**model, "family": HiddenInBf16(
                    model["family"], bf16)}, bf16)}
    asked = args.controls.split(",")
    if set(asked) - set(controls):
        ap.error(f"--controls: {sorted(set(asked) - set(controls))} unknown")
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
        ref, _ = ref_serve.generated_logits(model, seed, seqs, plens)
        gaps = res["obs"]["gaps"]
        line = {"workload": args.workload, "seed": seed,
                "program_gap_mean_sigma": float(gaps.mean()),
                "program_flips": int((gaps > 0).sum())}
        for name in asked:
            changed, quant = controls[name]
            low, _ = ref_serve.generated_logits(changed, seed, seqs, plens,
                                                quant=quant)
            gaps = check.served_gap(ref, jax.device_get(low.argmax(-1)))
            line[f"control_{name}_gap_mean_sigma"] = float(gaps.mean())
            line[f"control_{name}_flips"] = int((gaps > 0).sum())
            line[f"control_{name}_logit_sigma"] = float(
                (jnp.sqrt(((low - ref) ** 2).mean(-1)) / ref.std(-1)).mean())
            del low
        del ref
        line.update({"tokens": int(len(gaps)),
                     "program_correct": res["correct"],
                     "limit": cell["limits"]["served_gap_mean_sigma"]})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
