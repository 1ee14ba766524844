"""bench.py --preflight: the no-chip de-risking of TPU-oriented configs
(VERDICT r3 item 2).

Chip time for the flagship ``big_lm`` config is budgeted; these tests keep
the preflight machinery itself honest so a chip run is never wasted on a
shape error or a preflight regression.  The fast test drives the generic
machinery on the small
``lm`` config; the slow test runs the real ``big_lm`` preflight
(CPU compile of the 12-layer step + the 2-layer same-shape-class smoke,
~90 s on the single core).
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_preflight_lm_fast(tmp_path):
    out = tmp_path / "pf.json"
    rec = bench.preflight_config("lm", out_path=str(out))
    assert rec["ok"] is True
    assert rec["eval_shape_ok"] and rec["lower_compile_ok"]
    # the tiny LM trivially fits; the budget fields must be real numbers
    assert rec["fits_hbm"] is True
    assert rec["param_bytes"] > 1e6
    assert rec["projected_hbm_bytes"] >= (rec["param_bytes"]
                                          + rec["opt_state_bytes"])
    # artifact written and JSON-round-trippable
    on_disk = json.loads(out.read_text())
    assert on_disk["metric"] == "lm_preflight"


@pytest.mark.slow
def test_preflight_big_lm(tmp_path):
    """The flagship config traces, compiles on the CPU proxy and trains
    its same-shape-class smoke.  ``fits_hbm`` is the XLA:CPU proxy's
    reading and is recorded, not gated on: it over-reads the committed
    no-remat step (17 GB of temps where the TPU compiler counts 3.6 GB —
    tests/test_chip_compile.py and chip_smoke.py own that question)."""
    rec = bench.preflight_config("big_lm", out_path=str(tmp_path / "pf.json"))
    assert rec["ok"] is True, rec
    assert isinstance(rec["fits_hbm"], bool)
    smoke = rec["smoke"]
    assert smoke["ok"] is True, smoke
    # init loss near ln(32768): the smoke shares every matmul shape class
    assert abs(smoke["losses"][0] - smoke["ln_vocab"]) < 1.0
    # the sweep's chunked-CE MFU bets must stay de-risked: chunking
    # shrinks temps at fixed (batch, remat), and b16+chunk+remat stays
    # in budget.  No-remat rows are recorded but not gated — the CPU
    # proxy is known-pessimistic there (the chip runs b8 no-remat where
    # the proxy reads 17 GB).
    variants = {(v["batch"], v["ce_chunk"], v["remat"]): v
                for v in rec["ce_chunk_variants"]}
    assert variants[(16, 256, True)]["fits_hbm"] is True, variants
    # chunking must shrink temps at FIXED remat — both settings
    assert (variants[(8, 256, True)]["temp_bytes"]
            < variants[(8, 0, True)]["temp_bytes"]), variants
    assert (variants[(8, 256, False)]["temp_bytes"]
            < variants[(8, 0, False)]["temp_bytes"]), variants
