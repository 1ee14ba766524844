"""Speculative decoding (greedy): a small DRAFT model proposes k tokens,
the TARGET verifies all k in ONE chunked forward, and the longest agreeing
prefix is accepted plus the target's own correction token.

Why it belongs in a TPU serving stack: autoregressive decode runs one
bandwidth-bound (B, 1) step per token on the big model, while a chunked
verify runs k+1 positions through the SAME weights for nearly the same
HBM traffic as one step (weights stream once either way; the MXU eats
the extra rows).  With an accept rate a, the target pays roughly
ceil(N / (accepted-per-round)) chunk passes instead of N steps — the
classic latency lever when a cheap draft tracks the target well.

Greedy speculation is EXACT: every emitted token is argmax of the
target's logits at its position (accepted proposals by the verify
comparison, corrections directly), so the output is identical to
``generate(target, ...)`` token for token — pinned by
tests/test_speculative.py, not just asserted here.  One honest caveat:
the verify pass computes those logits in an (r+1)-wide chunk while
``generate`` uses (B, 1) steps — different XLA programs, so floats may
reassociate and a NEAR-TIE argmax can in principle flip.  Trained
models have logit margins that make this unobservable (the tests pin
bitwise equality), but UNTRAINED models' near-flat logits do flip ties
— visible as a sub-1 self-draft accept rate in the bench's mechanism
row, which is a tie-stability artifact, not a speculation bug.
Temperature speculation (``temperature > 0`` + a PRNG key) uses the
rejection-sampling correction of Leviathan et al. 2023
(:func:`accept_proposals`): each draft sample is accepted with
probability ``min(1, p/q)`` and the first rejection resamples from the
residual ``norm(max(0, p − q))``, so committed tokens are distributed
EXACTLY as target samples — pinned statistically on the pure numpy
core.  Greedy (``temperature == 0``) keeps the argmax-equality
contract above.

Cache bookkeeping rides the same invariant as the server's bucketed
prefill: positions past the accepted point hold stale K/V from rejected
proposals, but decode masks keys ``<= pos`` and every position is
REWRITTEN by the pass that next visits it before it becomes visible, so
no rewind is ever needed — "rollback" is free.  ONE position escapes
that invariant: after a fully-accepted round the draft never saw its
own last proposal (the round advances past it, so no later pass
rewrites it), which would leave a permanent ZERO draft-K/V entry that
every subsequent draft step attends.  Both paths therefore run a single
catch-up draft step there (see the ``n_acc == r`` blocks), keeping the
draft cache dense — pinned by the draft-cache-density regression tests.

Both models run their standard chunked forward
(``models.generate._forward_chunk``), so GQA, RoPE, SwiGLU, int8
weights, and the int8 KV cache all compose with speculation untouched.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .generate import _forward_chunk, init_kv_cache
from .transformer import Transformer


def _softmax(logits: np.ndarray, temperature: float) -> np.ndarray:
    z = logits.astype(np.float64) / temperature
    z -= z.max(-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(-1, keepdims=True)


def accept_proposals(p_logits: np.ndarray, q_logits: np.ndarray,
                     proposals: np.ndarray, temperature: float,
                     rng: np.random.Generator) -> Tuple[int, int]:
    """The Leviathan et al. 2023 rejection-sampling core for ONE batch
    row: proposals ``x_i ~ q_i`` are accepted with probability
    ``min(1, p_i(x_i) / q_i(x_i))``; at the first rejection the bonus
    token is drawn from the residual ``norm(max(0, p_i − q_i))``; if all
    r proposals survive, the bonus comes from the target's own
    ``p_{r}``.  Returns ``(n_accepted, bonus_token)``.

    The committed sequence ``x_0..x_{n-1}, bonus`` is distributed
    EXACTLY as n+1 ancestral samples from the target at this
    temperature — the marginal-exactness property pinned statistically
    by tests/test_speculative.py::test_acceptance_core_preserves_target
    (a pure-numpy function so the test can afford 10^5 trials).

    Shapes: ``p_logits (r+1, V)`` (target logits at the r proposal slots
    plus the bonus slot), ``q_logits (r, V)`` (draft logits the
    proposals were sampled from), ``proposals (r,)``.
    """
    r = proposals.shape[0]
    p = _softmax(p_logits, temperature)          # (r+1, V)
    q = _softmax(q_logits, temperature)          # (r, V)
    for i in range(r):
        x = int(proposals[i])
        if rng.random() < min(1.0, p[i, x] / max(q[i, x], 1e-38)):
            continue
        residual = np.maximum(p[i] - q[i], 0.0)
        total = residual.sum()
        if total <= 0:                            # p == q: accept x
            return i, x
        return i, int(rng.choice(p.shape[-1], p=residual / total))
    return r, int(rng.choice(p.shape[-1], p=p[r]))


@functools.lru_cache(maxsize=64)
def _chunk_program(model: Transformer, max_len: int, chunk: int,
                   kv_quant: bool):
    """One jitted (params, caches, ids (B, chunk), pos) -> (logits,
    caches) per (model, shapes): position is TRACED, so draft steps and
    verify chunks at every position share one compiled program each."""
    model.cfg.require_plain_block("speculative decoding")

    def run(params, caches, ids, pos):
        return _forward_chunk(model, params, caches, ids, pos)

    return jax.jit(run)


def speculative_generate(target: Transformer, target_params,
                         draft: Transformer, draft_params,
                         prompt: jax.Array, max_new_tokens: int,
                         k: int = 4, kv_quant: bool = False,
                         temperature: float = 0.0,
                         key: Optional[jax.Array] = None,
                         debug_state: Optional[dict] = None
                         ) -> Tuple[jax.Array, dict]:
    """Speculative decode -> ``(tokens (B, P + N), stats)``.

    ``temperature == 0`` (default) is greedy: output equals
    ``generate(target, ...)`` token for token.  ``temperature > 0``
    REQUIRES ``key`` and samples with the rejection-sampling correction
    (:func:`accept_proposals`), so committed tokens are distributed as
    target samples at that temperature; the decode is deterministic
    given ``(key, inputs)``.

    ``stats`` reports ``target_passes`` (chunked verifies the target ran,
    vs ``max_new_tokens`` single steps without speculation),
    ``draft_steps``, and ``accept_rate`` (accepted_total /
    proposed_total — tail rounds propose fewer than k, so the
    denominator is what was actually proposed).  The draft must share the target's vocabulary; batch
    rows are verified in lockstep (a row's round accepts the minimum of
    its own agreement — B=1 recovers the per-stream optimum, and larger
    B trades some accept rate for batching, the standard tradeoff).
    """
    if target.cfg.vocab_size != draft.cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft.cfg.vocab_size} != target vocab "
            f"{target.cfg.vocab_size}")
    use_temp = temperature > 0
    if use_temp and key is None:
        raise ValueError("temperature speculation needs a PRNG key")
    # numpy rng streams derived from the jax key: one per (round, row),
    # shared by the draft's sampling and the acceptance draws — the
    # whole decode is deterministic given (key, inputs)
    key_ints = ([int(x) for x in
                 np.asarray(jax.random.key_data(key)).ravel()]
                if use_temp else [])
    b, p = prompt.shape
    if max_new_tokens <= 0:   # mirror generate(): nothing to decode
        return jnp.asarray(prompt, jnp.int32), {
            "target_passes": 0, "draft_steps": 0, "rounds": 0,
            "accepted_total": 0, "proposed_total": 0, "accept_rate": 0.0}
    total = p + max_new_tokens
    for name, m in (("target", target), ("draft", draft)):
        if total > m.cfg.max_seq_len:
            raise ValueError(f"prompt {p} + {max_new_tokens} exceeds "
                             f"{name} max_seq_len {m.cfg.max_seq_len}")
    k = max(1, min(int(k), max_new_tokens))

    d_step = _chunk_program(draft, total, 1, kv_quant)
    t_caches = init_kv_cache(target, b, total, quant=kv_quant)
    d_caches = init_kv_cache(draft, b, total, quant=kv_quant)

    tokens = np.zeros((b, total), np.int32)
    tokens[:, :p] = np.asarray(prompt, np.int32)

    # prefill both models; the target's last-position argmax is token p
    t_prefill = _chunk_program(target, total, p, kv_quant)
    d_prefill = _chunk_program(draft, total, p, kv_quant)
    logits, t_caches = t_prefill(target_params, t_caches,
                                 jnp.asarray(tokens[:, :p]), 0)
    if use_temp:
        last = np.asarray(logits[:, -1])
        rng0 = np.random.default_rng(key_ints + [0xFEED])
        tokens[:, p] = [int(rng0.choice(last.shape[-1],
                                        p=_softmax(last[row], temperature)))
                       for row in range(b)]
    else:
        tokens[:, p] = np.asarray(jnp.argmax(logits[:, -1], -1))
    _, d_caches = d_prefill(draft_params, d_caches,
                            jnp.asarray(tokens[:, :p]), 0)

    pos = p            # index of the newest COMMITTED token
    stats = {"target_passes": 1, "draft_steps": 0, "rounds": 0,
             "accepted_total": 0, "proposed_total": 0}
    while pos < total - 1:
        r = min(k, total - 1 - pos)
        rngs = ([np.random.default_rng(key_ints + [stats["rounds"], row])
                 for row in range(b)] if use_temp else None)
        # --- draft proposes r tokens autoregressively ------------------
        proposals = np.zeros((b, r), np.int32)
        q_store = (np.zeros((b, r, target.cfg.vocab_size), np.float32)
                   if use_temp else None)
        cur = tokens[:, pos]
        for i in range(r):
            dl, d_caches = d_step(draft_params, d_caches,
                                  jnp.asarray(cur[:, None]), pos + i)
            if use_temp:
                dl_np = np.asarray(dl[:, -1])
                q_store[:, i] = dl_np
                cur = np.asarray(
                    [rngs[row].choice(dl_np.shape[-1],
                                      p=_softmax(dl_np[row], temperature))
                     for row in range(b)], np.int32)
            else:
                # greedy transfers only the (B,) argmax ints — never the
                # full logits row — on the latency-critical loop
                cur = np.asarray(jnp.argmax(dl[:, -1], -1), np.int32)
            proposals[:, i] = cur
            stats["draft_steps"] += 1
        # --- target verifies the r proposals in one chunk --------------
        # chunk = committed token at pos followed by the r proposals;
        # logits[i] are the target's prediction for position pos+1+i.
        # NO padding to a fixed width: a padded chunk near the sequence
        # end would write K/V past `total`, and dynamic_update_slice
        # CLAMPS the start index — silently corrupting earlier
        # positions.  The lru-cached program compiles once per distinct
        # r (k in steady state plus at most k-1 tail shapes).
        chunk = np.concatenate([tokens[:, pos:pos + 1], proposals], 1)
        vl, t_caches = _chunk_program(target, total, r + 1, kv_quant)(
            target_params, t_caches, jnp.asarray(chunk), pos)
        if use_temp:
            # per-row rejection sampling (accept_proposals), then batch
            # rows commit in LOCKSTEP at the minimum accepted count: a
            # row that accepted past the cut commits its accepted
            # proposal at the cut slot (a valid target draw), a row cut
            # at its own rejection commits its residual/bonus sample —
            # either way the committed tokens stay target-distributed
            vl_np = np.asarray(vl)
            accepts, bonuses = [], []
            for row in range(b):
                a_row, bonus = accept_proposals(
                    vl_np[row, :r + 1], q_store[row], proposals[row],
                    temperature, rngs[row])
                accepts.append(a_row)
                bonuses.append(bonus)
            n_acc = int(min(accepts))
            nxt = np.asarray(
                [proposals[row, n_acc] if accepts[row] > n_acc
                 else bonuses[row] for row in range(b)], np.int32)
        else:
            want = np.asarray(jnp.argmax(vl[:, :r + 1], -1), np.int32)
            # accepted prefix: proposals[i] == target argmax at that
            # slot, batch rows in lockstep (min across rows)
            agree = proposals == want[:, :r]
            n_acc = int(min((np.argmin(row) if not row.all() else r)
                            for row in agree))
            nxt = want[:, n_acc]
        # commit accepted proposals + the next token (the bonus slot may
        # not EXIST when the tail round's proposals were all accepted
        # and land exactly on the last position)
        round_pos = pos
        if n_acc:
            tokens[:, pos + 1:pos + 1 + n_acc] = proposals[:, :n_acc]
        if pos + 1 + n_acc < total:
            tokens[:, pos + 1 + n_acc] = nxt
            pos += n_acc + 1
        else:
            pos += n_acc
        if n_acc == r and pos < total - 1:
            # fully-accepted round: the draft loop fed positions
            # round_pos .. round_pos+r-1, so the LAST proposal's position
            # (round_pos + r, now a committed token) has no draft K/V —
            # and the next round starts at round_pos + r + 1 (the bonus),
            # so unlike a rejection it would never be rewritten: every
            # later draft step would attend a zero K/V entry there.  One
            # catch-up draft step (logits discarded) keeps the draft
            # cache dense (regression: tests/test_speculative.py
            # draft-cache-density tests).
            _, d_caches = d_step(draft_params, d_caches,
                                 jnp.asarray(proposals[:, r - 1:r]),
                                 round_pos + r)
            stats["draft_steps"] += 1
        stats["target_passes"] += 1
        stats["rounds"] += 1
        stats["accepted_total"] += n_acc
        stats["proposed_total"] += r
        # stale draft/target cache entries past `pos` are rewritten
        # before the mask can expose them (module docstring) — no rewind
    stats["accept_rate"] = (stats["accepted_total"]
                            / max(1, stats["proposed_total"]))
    if debug_state is not None:
        # test hook (draft-cache-density regression): final caches + pos
        debug_state.update(d_caches=d_caches, t_caches=t_caches, pos=pos)
    return jnp.asarray(tokens), stats


# ---------------------------------------------------------------------------
# Device-side greedy speculation: the WHOLE decode as one compiled program
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _spec_device_program(target: Transformer, draft: Transformer,
                         total: int, p: int, k: int, b: int,
                         debug_caches: bool = False):
    """One jitted (t_params, d_params, prompt) -> (tokens, stats-pytree)
    program for the whole greedy speculative decode (round 5).

    The host-loop :func:`speculative_generate` pays ~2 host dispatches
    per draft token plus a device->host logits round trip per round,
    which can outweigh the target passes it saves (not measured on a
    chip).  The TPU-first fix is structural:
    draft proposals run as a ``lax.scan``, greedy acceptance (an argmax
    prefix-agreement count) runs on device, and rounds run under
    ``lax.while_loop`` — zero host traffic until the final tokens.

    Greedy acceptance on device: the round's verify chunk yields the
    target's argmax ``want`` at all k+1 slots; proposals agree on a
    prefix of length ``n_acc = min_rows(sum(cumprod(agree)))`` and the
    committed block is exactly ``want[:, :n_acc+1]`` (accepted
    proposals EQUAL ``want`` there, the bonus is ``want[n_acc]``), so
    the program writes ``want`` wholesale and advances ``pos`` by
    ``n_acc+1`` — positions past the advance hold garbage that the next
    visit REWRITES before the causal mask can expose it, the module's
    standard no-rewind invariant.  Full rounds run while ``pos < total
    - 1 - k`` (a k+1 chunk never writes past the buffer); the <= k
    remaining tokens finish as predicated single steps inside the same
    program."""
    for m in (target, draft):
        m.cfg.require_plain_block("speculative decoding")

    def run(t_params, d_params, prompt):
        i32 = jnp.int32
        t_caches = init_kv_cache(target, b, total)
        d_caches = init_kv_cache(draft, b, total)
        tokens = jnp.zeros((b, total), i32)
        tokens = jax.lax.dynamic_update_slice(tokens,
                                              prompt.astype(i32), (0, 0))
        tl, t_caches = _forward_chunk(target, t_params, t_caches,
                                      prompt, 0)
        tokens = tokens.at[:, p].set(
            jnp.argmax(tl[:, -1], -1).astype(i32))
        _, d_caches = _forward_chunk(draft, d_params, d_caches, prompt, 0)

        st = dict(tokens=tokens, pos=jnp.asarray(p, i32),
                  t_caches=t_caches, d_caches=d_caches,
                  rounds=jnp.zeros((), i32),
                  accepted=jnp.zeros((), i32),
                  fills=jnp.zeros((), i32))

        def full_cond(st):
            return st["pos"] < total - 1 - k

        def full_round(st):
            pos = st["pos"]
            cur0 = jax.lax.dynamic_slice(st["tokens"], (0, pos), (b, 1))

            def d_tick(carry, i):
                cur, dc = carry
                dl, dc = _forward_chunk(draft, d_params, dc,
                                        cur[:, None], pos + i)
                nxt = jnp.argmax(dl[:, -1], -1).astype(i32)
                return (nxt, dc), nxt

            (_, d_caches), props = jax.lax.scan(
                d_tick, (cur0[:, 0], st["d_caches"]), jnp.arange(k))
            props = jnp.swapaxes(props, 0, 1)              # (B, k)
            chunk = jnp.concatenate([cur0, props], axis=1)  # (B, k+1)
            vl, t_caches = _forward_chunk(target, t_params,
                                          st["t_caches"], chunk, pos)
            want = jnp.argmax(vl, -1).astype(i32)           # (B, k+1)
            agree = (props == want[:, :k]).astype(i32)
            n_acc = jnp.min(jnp.sum(jnp.cumprod(agree, axis=1), axis=1))

            def fill_last_kv(dc):
                # fully-accepted round: the draft scan fed positions
                # pos..pos+k-1, leaving the last proposal's position
                # (pos + k, committed when n_acc == k) with ZERO draft
                # K/V that no later visit rewrites (the next round starts
                # at pos + k + 1) — run one catch-up draft step so later
                # rounds never attend a zero entry.  pos + k < total - 1
                # by full_cond, so the write stays in-buffer.  On a
                # partial accept the entry IS rewritten before it becomes
                # visible (the standard no-rewind invariant), so the cond
                # skips the extra forward.
                _, dc = _forward_chunk(draft, d_params, dc,
                                       props[:, k - 1:k], pos + k)
                return dc

            d_caches = jax.lax.cond(n_acc == k, fill_last_kv,
                                    lambda dc: dc, d_caches)
            tokens = jax.lax.dynamic_update_slice(st["tokens"], want,
                                                  (0, pos + 1))
            return dict(tokens=tokens, pos=pos + n_acc + 1,
                        t_caches=t_caches, d_caches=d_caches,
                        rounds=st["rounds"] + 1,
                        accepted=st["accepted"] + n_acc,
                        fills=st["fills"] + (n_acc == k).astype(i32))

        st = jax.lax.while_loop(full_cond, full_round, st)

        def t_tick(carry, _):
            tokens, tc, pos, steps = carry
            cur = jax.lax.dynamic_slice(tokens, (0, pos), (b, 1))
            tl, tc = _forward_chunk(target, t_params, tc, cur, pos)
            nxt = jnp.argmax(tl[:, -1], -1).astype(i32)
            live = pos < total - 1
            tokens = jnp.where(
                live,
                jax.lax.dynamic_update_slice(tokens, nxt[:, None],
                                             (0, pos + 1)),
                tokens)
            pos = jnp.where(live, pos + 1, pos)
            steps = steps + live.astype(i32)
            return (tokens, tc, pos, steps), None

        (tokens, _, pos, tail_steps), _ = jax.lax.scan(
            t_tick, (st["tokens"], st["t_caches"], st["pos"],
                     jnp.zeros((), jnp.int32)), None, length=k)
        stats = dict(rounds=st["rounds"], accepted=st["accepted"],
                     tail_steps=tail_steps, fills=st["fills"])
        if debug_caches:
            # test hook (draft-cache-density regression): the ring-phase
            # draft cache rides out of the jitted program
            return tokens, stats, (st["d_caches"], st["pos"])
        return tokens, stats

    return jax.jit(run)


def speculative_generate_device(target: Transformer, target_params,
                                draft: Transformer, draft_params,
                                prompt: jax.Array, max_new_tokens: int,
                                k: int = 4) -> Tuple[jax.Array, dict]:
    """Greedy speculative decode as ONE compiled program (see
    :func:`_spec_device_program`) -> ``(tokens (B, P+N), stats)`` with
    the host-loop's stats schema.  Output is token-identical to
    ``generate(target, ...)`` and to the host-loop
    :func:`speculative_generate` — same acceptance rule, same commits —
    pinned by tests/test_speculative.py on trained and untrained pairs.
    Temperature/kv-quant stay on the host-loop path (the numpy
    rejection-sampling core is the pinned exactness reference)."""
    if target.cfg.vocab_size != draft.cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft.cfg.vocab_size} != target vocab "
            f"{target.cfg.vocab_size}")
    b, p = prompt.shape
    if max_new_tokens <= 0:
        return jnp.asarray(prompt, jnp.int32), {
            "target_passes": 0, "draft_steps": 0, "rounds": 0,
            "accepted_total": 0, "proposed_total": 0, "accept_rate": 0.0}
    total = p + max_new_tokens
    for name, m in (("target", target), ("draft", draft)):
        if total > m.cfg.max_seq_len:
            raise ValueError(f"prompt {p} + {max_new_tokens} exceeds "
                             f"{name} max_seq_len {m.cfg.max_seq_len}")
    k = max(1, min(int(k), max_new_tokens))
    tokens, dstats = _spec_device_program(target, draft, total, p, k, b)(
        target_params, draft_params, jnp.asarray(prompt, jnp.int32))
    rounds = int(dstats["rounds"])
    accepted = int(dstats["accepted"])
    tail = int(dstats["tail_steps"])
    fills = int(dstats["fills"])
    stats = {
        "target_passes": 1 + rounds + tail,   # prefill + verifies + tail
        # proposals + the catch-up forward per fully-accepted round (the
        # draft-KV density fill) — same accounting as the host path
        "draft_steps": k * rounds + fills,
        "rounds": rounds,
        "accepted_total": accepted,
        "proposed_total": k * rounds,
        "accept_rate": accepted / max(1, k * rounds),
        "tail_steps": tail,
    }
    return tokens, stats
