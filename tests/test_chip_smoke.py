"""CPU rehearsal of chip_smoke.py's phases (on-chip-measurement §2, steps 1
and 2): the same functions the chip run calls, at a tiny size, with the
Pallas kernels in interpret mode — and the ``--chips 4`` path on four of
the virtual devices.  The script itself has no CPU path; the steering is
here, in the arguments."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = dict(vocab_size=64, seq_len=64, n_layers=2, d_model=32, n_heads=4,
            d_ff=64)
TINY_SERVE = dict(slots=4, num_blocks=33, block_size=8, prefill_chunk=16,
                  prompt_lens=(3, 9, 20, 30), max_new=(4, 6),
                  compute_dtype="float32")


def test_train_phase(tmp_path):
    out = chip_smoke.train(tmp_path, batch_size=8, steps=3, platform="cpu",
                           **TINY)
    assert out["steps"] == 3 and len(out["losses"]) == 3
    assert all(math.isfinite(x) for x in out["losses"])
    assert abs(out["losses"][0] - math.log(64)) < 1.0
    # off the chip the kernel is the interpreter, and the phase says so
    assert out["flash_lowering"] == "interpret"
    assert out["compile_s"]["train_step[dp]"] > 0
    json.dumps(out)      # every phase result must fit on a JSON line


@pytest.mark.parametrize("attn_impl", [None, "fused"],
                         ids=["default", "fused"])
def test_serve_phase(tmp_path, attn_impl):
    out = chip_smoke.serve(tmp_path, attn_impl, **TINY, **TINY_SERVE)
    assert out["attn_impl"] == (attn_impl or "gathered")
    assert out["requests"] == 4 and out["allocator_drained"]
    assert out["generated_tokens"] == sum(out["max_new"])
    # prefill buckets 8 and 16, the one first-token program, the decode
    # step, the admission and the take of a finished row; none after warm-up
    assert out["compiles_warmup"] == 6 and out["compiles_after_warmup"] == 0
    # f32 on the CPU: the served tokens ARE the reference argmax
    assert out["argmax_matches"] == out["generated_tokens"]
    assert out["worst_gap_sigma"] < 1e-3
    json.dumps(out)


def test_train_dp_phase_on_four_virtual_devices(tmp_path):
    out = chip_smoke.train_dp(tmp_path, n_devices=4, batch_size=8, steps=3,
                              **TINY)
    assert out["max_rel_diff"] < 1e-4
    assert out["devices_per_param_leaf"] == 4
    assert set(out["devices_per_batch_leaf"].values()) == {4}
    assert len(out["loss_pairs"]) == 3
    json.dumps(out)


def test_script_fails_in_phase_device_without_a_tpu():
    """``python chip_smoke.py`` has no CPU path: with JAX held to the CPU
    it exits non-zero in phase ``device``, says so, and prints no result."""
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=str(REPO))
    assert out.returncode != 0
    assert json.loads(out.stdout.splitlines()[0]) == {
        "phase": "device", "status": "start"}
    assert '"ok"' not in out.stdout
    assert "phase 'device' FAILED" in out.stderr
    assert "platform 'tpu' was asked for" in out.stderr
