"""The reducers that read the program's own names — ``scopes`` (device self
time by ``jax.named_scope`` path) and ``host_phases`` (device idle time by
``nnpt:`` span) — on small hand-made parsed traces, and ``xspace`` on a small
hand-written ``.xplane.pb``."""

import re

import pytest

from benchmark.reducers import host_phases, scopes, xplane, xspace

import xspace_writer

MS = 1_000_000
STEP = "jit(shard_step)/shard_map/"
FWD = STEP + "loss_and_grad/jvp(attention)/attn_dense/dot_general:"
BWD = STEP + "loss_and_grad/transpose(jvp(attention))/attn_dense/dot_general:"
CE = STEP + "loss_and_grad/jvp(chunked_ce)/while"
OPT = STEP + "optimizer_update/mul:"

OPS0 = [(FWD, 0, 10 * MS), (BWD, 10 * MS, 20 * MS),
        (CE, 30 * MS, 10 * MS),                         # a while ...
        (CE + "/body/dot_general:", 32 * MS, 6 * MS),   # ... and its body
        (OPT, 40 * MS, 4 * MS),
        ("", 44 * MS, 2 * MS),                          # a copy: no op_name
        (STEP + "mul:", 46 * MS, 1 * MS)]               # under no leaf scope
OPS1 = [(FWD, 0, 14 * MS), (OPT, 40 * MS, 8 * MS)]


def scoped_obs(devices, steps=2):
    trace = {p: {"ops": ops, "modules": mods,
                 "self": scopes.self_times(ops)}
             for p, (ops, mods) in devices.items()}
    return {"_scopes": trace, "traced_steps": steps}


TRAIN = {"/device:TPU:0": (OPS0, []), "/device:TPU:1": (OPS1, [])}


def test_self_time_goes_to_the_innermost_operation():
    own = {(p, s): t for p, s, t in scopes.self_times(OPS0)}
    assert own[(CE, 30 * MS)] == 4 * MS
    assert own[(CE + "/body/dot_general:", 32 * MS)] == 6 * MS
    assert sum(own.values()) == 47 * MS


def test_scope_is_matched_as_a_component_bare_or_transformed():
    rx = scopes.component("attention")
    assert rx.search(FWD) and rx.search(BWD)
    assert rx.search("jit(step)/attention/paged_gather/gather:")
    assert not rx.search("jit(step)/my_attention_v2/dot_general:")
    assert not scopes.component("attn").search(FWD)


def test_forward_and_backward_are_split_by_transpose():
    obs = scoped_obs(TRAIN)
    per = lambda **kw: scopes.scope_ms_per_step(obs, None, None, **kw)  # noqa: E731
    # mean over the two chips, per traced step
    assert per(scope="attention", phase="fwd") == pytest.approx((10 + 14) / 2 / 2)
    assert per(scope="attention", phase="bwd") == pytest.approx(20 / 2 / 2)
    assert per(scope="attention") == pytest.approx((30 + 14) / 2 / 2)
    assert per(scope="loss_and_grad", phase="fwd") == pytest.approx(
        (20 + 14) / 2 / 2)              # attention and the CE, body included
    assert per(scope="chunked_ce") == pytest.approx(10 / 2 / 2)
    assert per(scope="optimizer_update") == pytest.approx((4 + 8) / 2 / 2)
    with pytest.raises(ValueError):
        per(scope="attention", phase="sideways")


def test_absent_scope_reads_nothing_and_no_trace_reads_nothing():
    obs = scoped_obs(TRAIN)
    assert scopes.scope_ms_per_step(obs, None, None, scope="grad_exchange") \
        is None
    assert scopes.scope_ms_per_step({"_scopes": None, "traced_steps": 2},
                                    None, None, scope="attention") is None
    assert scopes.scope_ms_per_step(scoped_obs(TRAIN, steps=0), None, None,
                                    scope="attention") is None
    assert scopes.coverage({"_scopes": None}) is None


def test_operations_outside_the_named_module_are_left_out():
    gather = "jit(step)/attention/paged_gather/gather:"
    ops = [(gather, 1 * MS, 3 * MS), (gather, 21 * MS, 5 * MS),
           ("jit(prefill)/attention/paged_gather/gather:", 41 * MS, 7 * MS)]
    mods = [("jit_step(1)", 0, 10 * MS), ("jit_step(1)", 20 * MS, 10 * MS),
            ("jit_prefill(2)", 40 * MS, 10 * MS)]
    obs = scoped_obs({"/device:TPU:0": (ops, mods)})
    per = lambda **kw: scopes.scope_ms_per_module(obs, None, None, **kw)  # noqa: E731
    assert per(scope="paged_gather", module="jit_step") == pytest.approx(4.0)
    assert per(scope="paged_gather", module="jit_prefill") == pytest.approx(7.0)
    assert per(scope="paged_gather", module="jit_absent") is None
    assert per(scope="attn_core", module="jit_step") is None


def test_the_unplaced_remainder_is_returned():
    cov = scopes.coverage(scoped_obs(TRAIN))
    assert cov["busy_s"] == pytest.approx(0.047)
    assert cov["by_scope_s"] == pytest.approx(
        {"attention": 0.010, "attention:bwd": 0.020, "chunked_ce": 0.010,
         "optimizer_update": 0.004})
    assert cov["unplaced_s"] == pytest.approx(0.003)
    assert cov["unplaced_share"] == pytest.approx(3 / 47)
    assert cov["unplaced_top"][0] == ["(no op_name)", pytest.approx(0.002)]


# ---- host_phases -------------------------------------------------------------

EVENTS = sorted([("train_step", 100, 200), ("dispatch", 110, 190),
                 ("dispatch/submit", 120, 150), ("fetch", 300, 400),
                 ("train_step", 500, 600), ("dispatch", 510, 590)],
                key=lambda e: (e[1], -e[2]))


def phased_obs(events, gaps_by_chip):
    return {"_host_phases": {
        "events": events,
        "chips": [host_phases.attribute(events, g) for g in gaps_by_chip]}}


def test_nested_annotations_and_the_deepest_wins():
    got = host_phases.attribute(EVENTS, [(125, 145)])
    assert got["deepest"] == {"dispatch/submit": 20, None: 0}
    assert got["under"] == {"train_step": 20, "dispatch": 20,
                            "dispatch/submit": 20, None: 0}


def test_a_gap_is_shared_out_over_the_spans_it_overlaps():
    # 105..160: 5 in train_step alone, 10 in dispatch, 30 in submit, 10 in
    # dispatch again; 180..320: 10 dispatch, 10 train_step, 100 under no
    # span, 20 fetch
    got = host_phases.attribute(EVENTS, [(105, 160), (180, 320)])
    assert got["deepest"] == {"train_step": 15, "dispatch": 30,
                              "dispatch/submit": 30, None: 100, "fetch": 20}
    assert got["under"]["dispatch"] == 60 and got["under"][None] == 100


def test_idle_under_no_span_goes_to_null_and_zero_is_not_none():
    obs = phased_obs(EVENTS, [[(210, 290)], [(210, 250)]])
    per = lambda **kw: host_phases.idle_ms_per(obs, None, None, **kw)  # noqa: E731
    # (80 + 40) / 2 chips / 2 dispatches, in ms
    assert per(span=None, per="dispatch") == pytest.approx(30e-6)
    assert per(span="fetch", per="dispatch") == 0.0     # there, and no idle
    assert per(span="decode", per="dispatch") is None   # nowhere in the trace
    assert per(span="fetch", per="decode") is None
    assert host_phases.idle_ms_per({"_host_phases": None}, None, None,
                                   span=None, per="dispatch") is None
    assert host_phases.by_span(obs) == {"(none)": pytest.approx(80e-9)}


def test_children_count_under_their_parent():
    obs = phased_obs(EVENTS, [[(125, 145), (520, 540)]])
    assert host_phases.idle_ms_per(obs, None, None, span="dispatch",
                                   per="dispatch") == pytest.approx(20e-6)
    assert host_phases.idle_ms_per(obs, None, None, span="dispatch/submit",
                                   per="train_step") == pytest.approx(10e-6)


def test_the_loop_thread_is_the_line_with_the_loop_spans():
    lines = {("/host:CPU", "writer"): [("ckpt_write", 0, 50)],
             ("/host:CPU", "python3"): [("dispatch", 0, 10), ("load", 20, 30)]}
    assert host_phases.loop_line(lines) == lines[("/host:CPU", "python3")]
    assert host_phases.loop_line({("/host:CPU", "w"): [("load", 0, 1)]}) == []
    assert host_phases.idle_intervals(
        [("a", 0, 10), ("b", 5, 10), ("c", 30, 5)]) == [(15, 30)]


# ---- the .xplane.pb itself ----------------------------------------------------

def test_a_written_trace_is_read_back(tmp_path):
    """What the wire reader returns, ``ProfileData`` agrees with: names and
    times; the metadata's ``tf_op`` is what only the reader hands out."""
    path = tmp_path / "t.xplane.pb"
    xspace_writer.write(path, [
        xspace_writer.plane("/device:TPU:0", {
            "XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 5, 100,
                         {"tf_op": FWD}),
                        ("%copy-start.2 = copy-start(%q)", 200, 10, {})],
            "XLA Modules": [("jit_shard_step(7)", 0, 300, {})]}),
        xspace_writer.plane("/host:CPU", {
            "python3": [("nnpt:dispatch", 50, 100, {}),
                        ("bench:tick", 0, 400, {})]})])
    space = xspace.read(path, xplane.DEVICE_PLANE, lines=("XLA Ops",))
    dev = space["/device:TPU:0"]
    assert [(dev["metadata"][m].get("tf_op"), s, d)
            for m, s, d in dev["lines"]["XLA Ops"]] \
        == [(FWD, 5.0, 100.0), (None, 200.0, 10.0)]
    assert "XLA Modules" not in dev["lines"] and len(space) == 1
    assert scopes.parse(path)["/device:TPU:0"] == {
        "ops": [(FWD, 5.0, 100.0), ("", 200.0, 10.0)],
        "modules": [("jit_shard_step(7)", 0.0, 300.0)]}
    assert xplane.parse(path)["devices"]["/device:TPU:0"]["ops"] \
        == [("fusion.1", 5, 100), ("copy-start.2", 200, 10)]
    assert host_phases.parse_host(path) \
        == {("/host:CPU", "python3"): [("dispatch", 50, 150)]}
    assert xspace.read(path, re.compile("^/nothing"), lines=()) == {}
