#!/usr/bin/env bash
# Int8 serving, both halves (ops.quant + ops.qmm): train a tiny byte-LM,
# checkpoint it, then decode the SAME checkpoint four ways —
#   1. full precision,
#   2. --quantize int8 --kv_quant int8 (weights-only PTQ + int8 KV
#      cache: the BANDWIDTH half — int8 kernels + one f32 scale per
#      output channel, matmul still in the compute dtype),
#   3. --quantize int8 alone (the parity baseline for arm 4), and
#   4. --quantize int8 --matmul_dtype int8 (the COMPUTE half: a true
#      int8 activation x int8 weight dot with dynamic per-token
#      activation scales, int8 x int8 -> int32 on the MXU, both scales
#      folded on the output tile — ops/qmm.py, DESIGN.md §14).
# Arms 3 and 4 must agree on most greedy tokens (asserted below at the
# 60% tolerance DESIGN.md §14 states — on a trained model the per-token
# activation rounding can flip near-tie argmaxes, which then cascade;
# the random-init exact pin lives in tests/test_qmm.py).  The int8-compute
# arm is the one that also runs the arithmetic at int8 MXU rates on
# real hardware.  The reference has no inference path at all (its eval
# blocks are dead code, dataParallelTraining_NN_MPI.py:213-236).
set -euo pipefail
CKPT="$(mktemp -d)"
trap 'rm -rf "$CKPT"' EXIT

python -m neural_networks_parallel_training_with_mpi_tpu \
    --platform "${PLATFORM:-cpu}" --num_devices "${NUM_DEVICES:-8}" \
    --dataset lm --no-full-batch --batch_size 32 --nepochs 1 \
    --optimizer adam --lr 1e-3 --seq_len 32 --checkpoint_dir "$CKPT"

echo "--- full-precision decode"
python -m neural_networks_parallel_training_with_mpi_tpu \
    --platform "${PLATFORM:-cpu}" --num_devices "${NUM_DEVICES:-1}" \
    --dataset lm --seq_len 32 --checkpoint_dir "$CKPT" \
    --generate "10,20,30" --max_new_tokens 8

echo "--- int8 weights + int8 KV cache (same checkpoint; --quantize_skip
---     head keeps the logit projection exact, --kv_quant int8 stores the
---     KV cache as int8 with per-position scales)"
python -m neural_networks_parallel_training_with_mpi_tpu \
    --platform "${PLATFORM:-cpu}" --num_devices "${NUM_DEVICES:-1}" \
    --dataset lm --seq_len 32 --checkpoint_dir "$CKPT" \
    --generate "10,20,30" --max_new_tokens 8 \
    --quantize int8 --quantize_skip head --kv_quant int8

echo "--- int8 PTQ decode (parity baseline for the int8-compute arm)"
PTQ_TOKENS=$(python -m neural_networks_parallel_training_with_mpi_tpu \
    --platform "${PLATFORM:-cpu}" --num_devices "${NUM_DEVICES:-1}" \
    --dataset lm --seq_len 32 --checkpoint_dir "$CKPT" \
    --generate "10,20,30" --max_new_tokens 8 \
    --quantize int8 --quantize_skip head | tail -1)
echo "$PTQ_TOKENS"

echo "--- int8 COMPUTE decode (same PTQ weights; --matmul_dtype int8 runs
---     a true int8 activation x weight dot — ops/qmm.py — instead of
---     dequantizing into the compute-dtype matmul)"
QDOT_TOKENS=$(python -m neural_networks_parallel_training_with_mpi_tpu \
    --platform "${PLATFORM:-cpu}" --num_devices "${NUM_DEVICES:-1}" \
    --dataset lm --seq_len 32 --checkpoint_dir "$CKPT" \
    --generate "10,20,30" --max_new_tokens 8 \
    --quantize int8 --quantize_skip head \
    --matmul_dtype int8 | tail -1)
echo "$QDOT_TOKENS"

python - "$PTQ_TOKENS" "$QDOT_TOKENS" <<'PY'
import sys
a = [int(t) for t in sys.argv[1].split(",")]
b = [int(t) for t in sys.argv[2].split(",")]
assert len(a) == len(b) and a[:3] == b[:3], (a, b)  # prompt echo intact
agree = sum(x == y for x, y in zip(a[3:], b[3:])) / len(a[3:])
print(f"int8-compute vs PTQ greedy-token agreement: {agree:.0%}")
assert agree >= 0.6, f"agreement {agree:.0%} below the 60% tolerance"
PY
