#!/usr/bin/env bash
# Production-shaped serving: the continuous-batching scheduler (serve/)
# over the paged KV cache.  Ragged prompts arrive with per-request SLOs,
# the bounded queue admits them as slots+blocks free up, long prompts
# prefill in chunks INTERLEAVED with in-flight decode, and heterogeneous
# stream lengths share one block pool instead of each reserving max_len.
# Greedy results are token-identical to the single-stream generate()
# (pinned by tests/test_serve_paged.py); per-request TTFT/ITL print at
# the end (CPU numbers: they show the shape of the output, not a speed).
# The same request set then re-runs with attn_impl='fused' (the Pallas
# paged-attention kernel, interpret mode on CPU) and must emit the SAME
# tokens — the dispatch seam is invisible to clients.
set -euo pipefail

python - <<'EOF'
from neural_networks_parallel_training_with_mpi_tpu.utils import platform as plat

plat.pin("cpu", num_devices=1)
import jax.numpy as jnp
import numpy as np

from neural_networks_parallel_training_with_mpi_tpu.models import (
    Transformer, TransformerConfig, generate,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    Scheduler, ServeConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

model = Transformer(TransformerConfig(
    vocab_size=256, max_seq_len=128, n_layers=2, d_model=64, n_heads=4,
    d_ff=128))
params = model.init(prng.init_key(0))

# 8 streams max in the batched step; 33 blocks x 16 positions of KV pool
# shared by every stream (a dense slot server with this memory would
# hold FOUR 128-token streams; tests/test_serve_paged.py counts it).
# attn_impl toggles the attention dispatch: 'gathered' materializes
# pool[table]; 'fused' walks only allocated blocks in a Pallas kernel
cfg = dict(slots=8, num_blocks=33, block_size=16, prefill_chunk=32,
           queue_depth=16)
sched = Scheduler(model, params, ServeConfig(**cfg, attn_impl="gathered"))

# warmup: pay the (cached) prefill-bucket + decode-step compiles once,
# so the printed TTFT/ITL are steady-state serving numbers, not XLA
# compilation time
for plen in (3, 12, 24, 39):
    sched.submit(list(range(1, plen + 1)), 2)
sched.run_until_drained()

requests = [
    ([10, 20, 30], 24, 500.0),                  # short prompt, tight SLO
    (list(range(1, 40)), 16, None),             # 39-token prompt: chunked
    ([7, 8], 12, 1000.0),
    ([5, 9, 11, 13] * 6, 20, None),             # straddles block bounds
]
rids = {}
for prompt, n, slo in requests:
    rid = sched.submit(prompt, n, slo_ms=slo)
    assert rid is not None, "bounded queue rejected (raise queue_depth)"
    rids[rid] = (prompt, n)
print(f"queued {len(rids)} ragged requests "
      f"({sched.server.free_blocks} free KV blocks)")

order = sched.run_until_drained()
print(f"drained in {sched.tick_no} ticks, completion order {order}")

wants = {}
for rid, (prompt, n) in rids.items():
    got = sched.result(rid)
    want = [int(t) for t in np.asarray(
        generate(model, params, jnp.asarray([prompt], jnp.int32), n))[0]]
    assert got == want, (rid, got, want)
    wants[(tuple(prompt), n)] = want
    st = sched.stats(rid)
    print(f"req {rid}: prompt {len(prompt):>2} tok -> +{n:>2} tok   "
          f"TTFT {st.ttft_ms:7.1f} ms   ITL {st.itl_ms:5.1f} ms"
          + ("   (SLO met)" if st.slo_ms and not st.deadline_missed
             else ""))
sched.server.allocator.assert_drained()   # zero leaked blocks
sched.close()
print("paged continuous-batched tokens == single-stream generate() "
      "for all requests; block pool fully drained")

# same requests through the FUSED paged-attention kernel: the dispatch
# seam must not move a single token (checked against the SAME generate()
# references the gathered pass just verified — no second eager decode),
# and the attended-keys telemetry shows the work the kernel skips
fused = Scheduler(model, params, ServeConfig(**cfg, attn_impl="fused"))
fused_rids = {fused.submit(prompt, n, slo_ms=slo): (prompt, n)
              for prompt, n, slo in requests}
fused.run_until_drained()
for rid, (prompt, n) in fused_rids.items():
    got = fused.result(rid)
    assert got == wants[(tuple(prompt), n)], (rid, got)
ratio = fused.attended_keys / fused.padded_keys
print(f"fused kernel attended {fused.attended_keys} of "
      f"{fused.padded_keys} padded key positions "
      f"(ratio {ratio:.3f} — the skipped FLOPs)")
fused.server.allocator.assert_drained()
fused.close()
print("attn_impl=fused == attn_impl=gathered: token-identical end to end")
EOF
