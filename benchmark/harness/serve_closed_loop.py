"""Traffic kind ``serve_closed_loop``: a fixed number of callers, each sending
its next request when the last one completed, over ``Scheduler``.

The harness owns the loop (``submit`` and ``tick``), the clock the scheduler
stamps requests with, and the requests.  The lengths are a fixed trace (drawn
once from the mix's distributions with the mix's own ``shape_seed``) that every
run replays from its start, so every seed offers the same work in the same
order; the seed makes the token ids (and the weights).  With the order left to
the seed, the p90 of the time to the first token swung by a third between two
runs (619 and 884 ms, chip runs of PR 25): in a closed loop it is set by which
long prompts happen to queue behind each other.  Set-up warms every program the traffic can draw, then fills
the server until every caller's first request has sampled a token; the window
starts there and is stopped by the clock, in-flight requests abandoned.
"""

from __future__ import annotations

import gc
import shutil
import time

import numpy as np

from . import check, common, weights


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], size=n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Requests:
    """The request stream of one run, caller by caller."""

    def __init__(self, job: dict, vocab: int, seed: int):
        shape_rng = np.random.default_rng(job["shape_seed"])
        n = job["shape_pool"]
        self.prompt_lens = draw_lengths(job["prompt_len"], n, shape_rng)
        self.output_lens = draw_lengths(job["output_len"], n, shape_rng)
        self.rng = np.random.default_rng([int(seed), 11])
        self.vocab, self.i = vocab, 0

    def next(self):
        j = self.i % len(self.prompt_lens)
        self.i += 1
        prompt = self.rng.integers(0, self.vocab,
                                   size=int(self.prompt_lens[j])).tolist()
        return prompt, int(self.output_lens[j])


def well_formed(toks, prompt, n_new, vocab) -> bool:
    """The prompt echoed, as many new tokens as asked for, every id in range."""
    return (toks[:len(prompt)] == prompt and len(toks) == len(prompt) + n_new
            and all(0 <= t < vocab for t in toks))


def pick_sample(done: list, k: int, seed: int) -> list:
    """``k`` of the finished requests, drawn from the seed, the longest
    among them."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i][2]))
    others = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed), 13])
    pick = {longest, *rng.permutation(others)[:max(0, k - 1)].tolist()}
    return [done[i] for i in sorted(pick)]


def run(cell: dict, seed: int, seconds: float, trace: bool, dev: dict,
        t_process: float, out_dir=common.OUT, tamper=None,
        reference_kwargs=None) -> dict:
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig, prewarm,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger,
    )

    from ..reference import serve as ref_serve

    model, job = cell["model"], cell["job"]
    shutil.rmtree(out_dir / "serve_trace", ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    fam = model["family"]
    net = fam.program_model(model)
    maker = weights.Maker(model, seed)
    params = fam.to_program(model, maker.outer(), maker.layers())
    jax.block_until_ready(params)
    common.mark("weights on the device")
    serve_cfg = dict(job["serve_config"], seed=int(seed) & 0x7FFFFFFF)
    make_scheduler = lambda: Scheduler(                         # noqa: E731
        net, params, ServeConfig(**serve_cfg), now_fn=time.perf_counter)
    requests = Requests(job, model["vocab_size"], seed)
    clients = job["clients"]
    spans = []
    listener = lambda n, t, d, a: spans.append((n, t, d, dict(a or {})))  # noqa: E731
    profiler = common.Profiler(out_dir, job["trace"], trace)

    tracer = trace_lib.start_run(str(out_dir / "serve_trace"))
    sched = None
    try:
        ledger = compile_ledger.active()
        prewarm(make_scheduler, prompt_lens=(job["prompt_len"]["min"],
                                             job["prompt_len"]["max"]))
        common.mark("programs warm")
        sched = make_scheduler()
        if tamper is not None:
            tamper(sched)
        outstanding = [None] * clients        # rid a caller waits for
        sent = {}                             # rid -> (prompt, max_new)
        finished = []                         # (rid, Request, tokens)
        rejected = 0

        def submit_idle():
            nonlocal rejected
            for c in range(clients):
                if outstanding[c] is None:
                    prompt, n_new = requests.next()
                    rid = sched.submit(prompt, n_new)
                    if rid is None:
                        rejected += 1
                        continue
                    outstanding[c], sent[rid] = rid, (prompt, n_new)

        def tick():
            for rid in sched.tick():
                outstanding[outstanding.index(rid)] = None
                finished.append((rid, sched.stats(rid), sched.result(rid)))

        # ---- fill: until every caller's first request has a first token ----
        submit_idle()
        first = list(outstanding)
        while any(sched.stats(r).t_first is None for r in first):
            tick()
            submit_idle()
        jax.block_until_ready(sched.server.pos)
        n_fill = len(finished)
        keys0, compiles0 = sched.padded_keys, len(ledger.events)
        attended0 = sched.attended_keys
        trace_lib.add_listener(listener)
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        common.mark(f"window opens ({n_fill} requests done in the fill)")
        # ---- the window -----------------------------------------------------
        ticks = 0
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
            if profiler.due_start(elapsed):
                jax.block_until_ready(sched.server.pos)
                profiler.start()
            elif profiler.due_stop(elapsed):
                jax.block_until_ready(sched.server.pos)
                profiler.stop()
            submit_idle()
            with jax.profiler.TraceAnnotation("bench:tick"):
                tick()
            ticks += 1
        jax.block_until_ready(sched.server.pos)
        t_end = time.perf_counter()
        if profiler.running:
            profiler.stop()
        trace_lib.remove_listener(listener)
        keys1, compiles1 = sched.padded_keys, len(ledger.events)
        attended = sched.attended_keys - attended0
        t_cap = sched.server.t_cap
        events = list(ledger.events)
    finally:
        trace_lib.remove_listener(listener)
        if sched is not None:
            sched.close()
        trace_lib.stop_run(tracer)

    window_s = t_end - t0
    traced_out = window_s - profiler.stall_s    # what per-layer rates are over
    done = finished[n_fill:]        # completed inside the window
    live = [sched.stats(r) for r in outstanding if r is not None]
    begun = [st for st in [st for _, st, _ in finished] + live
             if st.t_first is not None and t0 <= st.t_first < t_end]
    firsts = len(begun)
    decode_tokens = (keys1 - keys0) // t_cap
    tokens = decode_tokens + firsts
    ttft = [(st.t_first - st.t_submit) * 1e3 for _, st, _ in done]
    itl = [(st.t_done - st.t_first) / max(1, st.max_new - 1) * 1e3
           for _, st, _ in done]
    peak = common.memory_peak_bytes(dev["devices"])

    # ---- what came back, judged once the window has closed ----------------
    malformed = sum(1 for rid, _st, toks in finished
                    if not well_formed(toks, *sent[rid], model["vocab_size"]))
    sample = pick_sample(done, job["check_requests"], seed)
    # free the program before the reference takes the chip's memory
    begun_sizes = [(len(st.prompt), st.max_new) for st in begun]
    decode_spans = sum(1 for s in spans if s[0] == "decode")
    slots = sched.cfg.slots
    sched = net = params = make_scheduler = None
    gc.collect()
    jax.clear_caches()
    common.mark("window closed, program freed")
    t_ref = time.perf_counter()
    if sample:
        logits, toks = ref_serve.generated_logits(
            model, seed, [t for _, _, t in sample],
            [len(sent[r][0]) for r, _, _ in sample],
            **(reference_kwargs or {}))
        gaps = check.served_gap(logits, toks)
    else:
        gaps = []
    ref_s = time.perf_counter() - t_ref
    checks = check.serve_checks(malformed, gaps, cell["limits"])
    checks.append(check.entry("compiles_in_window", compiles1 - compiles0,
                               0))
    n_done = len(done)
    common.say(
        f"serve: {n_done} requests completed in {window_s:.3f} s "
        f"({n_done / window_s:.2f}/s), {ticks} ticks, {tokens} tokens; "
        f"ttft p50 {common.percentile(ttft, 50) if ttft else None} ms, "
        f"itl p50 {common.percentile(itl, 50) if itl else None} ms; "
        f"rejected {rejected}; checked {len(sample)} requests, "
        f"{len(gaps)} tokens (gap mean {np.mean(gaps) if len(gaps) else None}"
        f", p99 {np.quantile(gaps, 0.99) if len(gaps) else None}, widest "
        f"{np.max(gaps) if len(gaps) else None}), reference {ref_s:.1f} s; "
        f"set-up {setup_s:.1f} s")
    e2e = {"serve_tokens_per_s": tokens / window_s, "setup_s": setup_s}
    if ttft:
        e2e["ttft_p90_ms"] = common.percentile(ttft, 90)
        e2e["itl_p90_ms"] = common.percentile(itl, 90)
    return {
        "correct": all(c["ok"] for c in checks) and n_done > 0,
        "checks": checks, "attempted": n_done + rejected, "failed": rejected,
        "end_to_end": e2e, "memory_peak_bytes": peak,
        "obs": {"spans": spans, "window_s": traced_out,
                "gap_default": "between ticks",
                "ticks": ticks, "decode_ticks": decode_spans,
                "decode_stream_ticks": decode_tokens, "slots": slots,
                "tokens": tokens, "requests": n_done,
                "begun_sizes": begun_sizes, "attended_keys": attended,
                "compile_s": common.compile_seconds(events[:compiles0]),
                "profiler": profiler, "gaps": gaps, "reference_s": ref_s,
                "sample": [(len(sent[r][0]), t) for r, _, t in sample]},
    }
