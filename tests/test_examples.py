"""The examples must actually run — the reference's one command works out
of the box (reference README.md:12) and so must ours.

Example 01 is the parity demo (the reference's exact job: 16-sample sklearn
regression, full-batch-ish SGD, 3 epochs, dataParallelTraining_NN_MPI.py:242-255);
it runs here end-to-end on the virtual 8-device CPU mesh via the CLI's
``--platform cpu --num_devices 8`` launch path.
"""

import os
import pathlib
import subprocess
import sys
import pytest

# integration-heavy: full lane only (core lane: -m 'not slow')
pytestmark = pytest.mark.slow

REPO = pathlib.Path(__file__).resolve().parent.parent


def _clean_env():
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        platform as plat,
    )

    env = dict(os.environ)
    # the scripts' own --platform cpu pin must be sufficient; give them the
    # raw environment, not the conftest's pre-pinned one
    env.pop("JAX_PLATFORMS", None)
    plat.force_host_device_count(None, env=env)
    return env


def test_example_01_reference_parity_completes():
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "01_reference_parity.sh")],
        capture_output=True, text=True, timeout=120, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stderr + out.stdout


def test_cli_platform_tpu_exits_2_and_names_what_it_found():
    """--platform tpu where JAX brings up a CPU exits 2 with one clear
    line naming what it found — no fallback, no training, no number."""
    env = dict(_clean_env(), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "neural_networks_parallel_training_with_mpi_tpu",
         "--platform", "tpu", "--nepochs", "1"],
        capture_output=True, text=True, timeout=180, env=env, cwd=str(REPO),
    )
    assert out.returncode == 2, out.stderr[-2000:]
    text = out.stdout + out.stderr
    assert "platform: cpu | device_kind: cpu" in text     # first log line
    assert "platform 'tpu' was asked for" in text
    assert "platform 'cpu'" in text
    assert "loss" not in text and "samples/sec" not in text


def test_example_08_sp_tp_completes():
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "08_sp_tp_3d.sh")],
        capture_output=True, text=True, timeout=240, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stderr + out.stdout


def test_cli_generate_from_checkpoint(tmp_path):
    """Train 1 epoch -> decode from the checkpoint via --generate: the
    inference entrypoint (the reference has none; its closest artifact is
    the dead test block at dataParallelTraining_NN_MPI.py:227-236)."""
    ck = str(tmp_path / "ck")
    common = ["--dataset", "lm", "--optimizer", "adam",
              "--platform", "cpu", "--num_devices", "8",
              "--checkpoint_dir", ck]
    train = subprocess.run(
        [sys.executable, "-m", "neural_networks_parallel_training_with_mpi_tpu",
         *common, "--no-full-batch", "--batch_size", "32", "--nepochs", "1"],
        capture_output=True, text=True, timeout=240, env=_clean_env(),
        cwd=str(REPO))
    assert train.returncode == 0, train.stderr[-2000:]
    # decode WITHOUT repeating the training-time --optimizer: restore
    # goes through the stored treedef, no template needed
    gen = subprocess.run(
        [sys.executable, "-m", "neural_networks_parallel_training_with_mpi_tpu",
         "--dataset", "lm", "--platform", "cpu", "--num_devices", "8",
         "--checkpoint_dir", ck,
         "--generate", "10,20,30", "--max_new_tokens", "8",
         "--temperature", "0.8", "--top_k", "20"],
        capture_output=True, text=True, timeout=240, env=_clean_env(),
        cwd=str(REPO))
    assert gen.returncode == 0, gen.stderr[-2000:]
    assert "restored step" in gen.stdout + gen.stderr
    toks = [int(t) for t in gen.stdout.strip().splitlines()[-1].split(",")]
    assert toks[:3] == [10, 20, 30] and len(toks) == 11
    assert all(0 <= t < 256 for t in toks)


def test_example_10_expert_tensor_completes():
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "10_expert_tensor.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stderr + out.stdout


def test_example_11_real_text_lm_completes():
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "11_real_text_lm.sh")],
        capture_output=True, text=True, timeout=360, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stderr + out.stdout


def test_example_12_interleaved_pipeline_completes():
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "12_interleaved_pipeline.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stderr + out.stdout


def test_example_13_tensor_parallel_serving_completes():
    """Trains on DP x SP x TP, decodes the checkpoint natively with
    generate_tp AND through the CLI's layout-reconciling dense path."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "13_tensor_parallel_serving.sh")],
        capture_output=True, text=True, timeout=600, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "native TP decode:" in out.stdout
    # last line: the CLI decode's comma-separated continuation ids
    last = out.stdout.strip().splitlines()[-1]
    ids = [int(t) for t in last.split(",")]
    assert len(ids) == 3 + 8 and ids[:3] == [10, 20, 30]


def test_cli_generate_reconciles_sp_tp_checkpoint(tmp_path):
    """A checkpoint written by the seq x tensor layout carries the
    head-aligned qkv permutation (meta qkv_tp=2); the CLI decode must
    unpermute it — its tokens must exactly match the native generate_tp
    decode of the same checkpoint (which consumes the permuted layout
    directly)."""
    ck = str(tmp_path / "ck")
    env = _clean_env()
    train = subprocess.run(
        [sys.executable, "-m",
         "neural_networks_parallel_training_with_mpi_tpu",
         "--platform", "cpu", "--num_devices", "8",
         "--dataset", "lm", "--seq_len", "32", "--no-full-batch",
         "--batch_size", "32", "--nepochs", "1", "--optimizer", "adam",
         "--lr", "1e-3", "--dp", "2", "--sp", "2", "--tp", "2",
         "--checkpoint_dir", ck],
        capture_output=True, text=True, timeout=420, env=env, cwd=str(REPO),
    )
    assert train.returncode == 0, train.stderr[-2000:]
    dec = subprocess.run(
        [sys.executable, "-m",
         "neural_networks_parallel_training_with_mpi_tpu",
         "--platform", "cpu", "--num_devices", "8",
         "--dataset", "lm", "--seq_len", "32",
         "--checkpoint_dir", ck, "--generate", "7,8,9",
         "--max_new_tokens", "6"],
        capture_output=True, text=True, timeout=240, env=env, cwd=str(REPO),
    )
    assert dec.returncode == 0, dec.stderr[-2000:]
    cli_ids = [int(t) for t in dec.stdout.strip().splitlines()[-1].split(",")]

    # oracle: native TP decode of the same checkpoint, in this process
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        MeshConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer, TransformerConfig, generate_tp,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        mesh as mesh_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        checkpoint as ckpt,
    )

    restored = ckpt.restore(ck, template=None)
    model = Transformer(TransformerConfig(
        vocab_size=256, max_seq_len=512, n_layers=2, d_model=128,
        n_heads=4, d_ff=512))
    mesh = mesh_lib.make_mesh(MeshConfig(data=2, tensor=2),
                              devices=np.asarray(jax.devices()[:4]))
    # rows must divide the data axis (2): duplicate the prompt row — each
    # batch row decodes independently, so row 0 equals the 1-row decode
    native = generate_tp(model, restored.params,
                         jnp.asarray([[7, 8, 9], [7, 8, 9]], jnp.int32),
                         mesh, max_new_tokens=6)
    assert cli_ids == [int(t) for t in np.asarray(native)[0]]


def test_example_14_four_axis_mesh_completes():
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "14_four_axis_mesh.sh")],
        capture_output=True, text=True, timeout=600, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stderr + out.stdout


def test_example_15_int8_quantized_serving_completes():
    """Trains, checkpoints, and decodes the same checkpoint full-precision,
    with --quantize int8 (weights-only PTQ, ops.quant) AND with the true
    int8-compute dot (--matmul_dtype int8, ops.qmm) — the script prints
    the PTQ-vs-int8-compute greedy-token agreement and asserts it at the
    DESIGN §14 tolerance (exactness on a trained model is a near-tie
    lottery; the random-init exact pin lives in tests/test_qmm.py)."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "15_int8_quantized_serving.sh")],
        capture_output=True, text=True, timeout=600, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    text = out.stderr + out.stdout
    assert "int8 weights-only PTQ: param bytes" in text
    assert "int8-compute vs PTQ greedy-token agreement" in text
    # all three decodes print prompt + 8 continuation ids (the PTQ and
    # int8-compute lines are echoed from captured variables)
    id_lines = [l for l in out.stdout.splitlines()
                if l.count(",") == 10 and l.replace(",", "").isdigit()]
    assert len(id_lines) >= 3, out.stdout


def test_example_16_continuous_batching_completes():
    """Staggered requests through the slot server; each must match its
    single-stream decode exactly (asserted inside the script)."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "16_continuous_batching.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "continuous-batched tokens == single-stream generate()" \
        in out.stdout


def test_example_17_modern_lm_stack_completes():
    """RoPE x SwiGLU x GQA trained via the CLI, then decoded from the
    checkpoint with int8 weights + int8 KV cache stacked."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "17_modern_lm_stack.sh")],
        capture_output=True, text=True, timeout=600, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    text = out.stderr + out.stdout
    assert "done: final loss" in text
    assert "int8 weights-only PTQ" in text
    last = out.stdout.strip().splitlines()[-1]
    ids = [int(t) for t in last.split(",")]
    assert ids[:3] == [10, 20, 30] and len(ids) == 11


def test_example_18_speculative_decoding_completes():
    """Trains a byte-LM, then self-draft speculative decode: tokens must
    equal plain greedy (asserted inside) with fewer target passes."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "18_speculative_decoding.sh")],
        capture_output=True, text=True, timeout=600, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tokens identical" in out.stdout
    assert "accept rate" in out.stdout


def test_example_19_multi_step_dispatch_completes():
    """Same job at --steps_per_dispatch 1 and 8: the script itself diffs
    the final loss lines and fails on any trajectory divergence."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "19_multi_step_dispatch.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "trajectory identical" in out.stdout


def test_example_21_anakin_rl_completes():
    """Gridworld PPO through the CLI end to end (rl/): the script itself
    asserts the trained return EMA beats the measured random-policy
    (lr=0) baseline AND that a checkpoint-resumed run lands on the
    bitwise-identical params of the uninterrupted trajectory."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "21_anakin_rl.sh")],
        capture_output=True, text=True, timeout=560, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "return improved over the random-policy baseline" in out.stdout
    assert "resume trajectory-exact" in out.stdout


def test_example_20_paged_serving_completes():
    """The serve/ subsystem end to end on CPU: ragged prompts with SLOs
    through the continuous-batching scheduler over the paged KV pool;
    the script itself asserts token parity with generate() and a fully
    drained block allocator (for BOTH attention impls — the fused
    Pallas kernel must be client-invisible), and prints per-request
    TTFT/ITL plus the attended-keys ratio the kernel skips."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "20_paged_serving.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "block pool fully drained" in out.stdout
    assert "TTFT" in out.stdout
    assert ("attn_impl=fused == attn_impl=gathered: token-identical "
            "end to end") in out.stdout
    assert "the skipped FLOPs" in out.stdout


def test_example_22_prefix_cached_serving_completes():
    """The prefix cache end to end on CPU: a shared-system-prompt mix
    with a regenerated turn (full hit + CoW fork) through cache-on and
    cache-off schedulers; the script itself asserts token identity
    against both the cache-off arm and generate(), refcount drain, a
    faster cached drain, and prints the per-request cold-vs-cached
    TTFTs plus the hit/fork counters."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "22_prefix_cached_serving.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert ("tokens: cache on == cache off == generate() for all "
            "5 requests") in out.stdout
    assert "CoW fork(s)" in out.stdout
    assert "near-zero-TTFT admission verified" in out.stdout
    assert "block pool fully drained" in out.stdout


def test_example_23_serving_fleet_completes():
    """The serving fleet end to end on CPU: 2 supervised subprocess
    replicas behind the SLO-aware router, a SIGKILL mid-load, requeue
    with byte-identical tokens (asserted in-script against the
    undisturbed single-scheduler reference), supervisor relaunch with
    the sibling undisturbed, and the merged per-replica obs_agg view."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "23_serving_fleet.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tokens byte-identical across the kill" in out.stdout
    assert ("supervisor: replica-0 relaunched; replica-1 undisturbed"
            in out.stdout)
    assert "per-writer" in out.stdout        # obs_agg breakdown rows


def test_example_24_fleet_autopilot_completes():
    """The fleet autopilot end to end on CPU, both arms: a mid-load
    weight push that promotes through canary -> judge -> grow -> drain
    (zero downtime, per-generation token attribution asserted
    in-script), and a TOCTOU-corrupted canary checkpoint that fails in
    the worker (exit 44) and rolls back with generation 0
    undisturbed."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "24_fleet_autopilot.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "rollout: promoted at t=" in out.stdout
    assert "zero downtime: all" in out.stdout
    assert "corrupt canary: rolled back at t=" in out.stdout
    assert "generation 0 undisturbed" in out.stdout


def test_example_25_preemption_drain_completes():
    """Notice-drain vs SIGKILL A/B on a 2-replica fleet: the same
    failure with and without the advance notice, over bitwise-identical
    traffic — the notice arm must requeue NOTHING (victim drains to
    exit 47, the autopilot backfills before it dies) while the SIGKILL
    arm requeues every in-flight request and redecodes their tokens."""
    out = subprocess.run(
        ["bash", str(REPO / "examples" / "25_preemption_drain.sh")],
        capture_output=True, text=True, timeout=420, env=_clean_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "notice arm: zero requeued requests" in out.stdout
    assert "requests requeued" in out.stdout          # the kill arm paid
    assert "identical traffic both arms" in out.stdout
