"""The trace and span reducers on a synthetic span list, a synthetic parsed
trace, and a small trace recorded here."""

import pytest

from benchmark.reducers import counters, spans, xplane


class FakeProfiler:
    t_start, t_stop = 10.0, 10.5

    def trace_file(self):
        return None


def obs_with(trace):
    return {"profiler": FakeProfiler(), "_trace": trace, "traced_steps": 2,
            "spans": [("dispatch", 0.0, 0.002, {}), ("dispatch", 1.0, 0.004, {}),
                      ("load", 0.5, 0.001, {}), ("prefill", 0.1, 0.25, {})],
            "window_s": 2.0, "decode_stream_ticks": 30, "decode_ticks": 10,
            "slots": 4, "compile_s": 1.5}


TRACE = {"devices": {
    "/device:TPU:0": {
        "ops": [("fusion.1", 0, 100_000_000), ("while", 150_000_000, 100_000_000),
                ("fusion.2", 160_000_000, 50_000_000),      # nested in the while
                ("all-reduce.3", 300_000_000, 20_000_000)],
        "modules": [("jit_step(1)", 0, 100_000_000),
                    ("jit_step(1)", 150_000_000, 120_000_000),
                    ("jit_prefill(2)", 300_000_000, 20_000_000)],
        "families": {"fusion": 150_000_000, "while": 50_000_000,
                     "all-reduce": 20_000_000}},
    "/device:TPU:1": {
        "ops": [("fusion.1", 0, 200_000_000), ("all-reduce.3", 300_000_000,
                                               40_000_000)],
        "modules": [], "families": {}}},
    "host": [("bench:tick", 90_000_000, 70_000_000)]}


def test_busy_is_a_union_averaged_over_chips():
    assert xplane.merged([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]
    # chip 0: 100 + 100 (the nested fusion adds nothing) + 20; chip 1: 240
    assert xplane.busy_seconds(TRACE) == pytest.approx((0.22 + 0.24) / 2)


def test_trace_reducers():
    obs = obs_with(TRACE)
    assert xplane.idle_share(obs, None, None) == pytest.approx(
        100 * (1 - 0.23 / 0.5))
    assert xplane.busy_ms_per_step(obs, None, None) == pytest.approx(115.0)
    assert xplane.op_ms_per_step(obs, None, None, pattern="all-reduce") \
        == pytest.approx((20 + 40) / 2 / 2)
    assert xplane.op_ms_per_step(obs, None, None, pattern="nothing") is None
    assert xplane.module_median_ms(obs, None, None, module="jit_step") \
        == pytest.approx(110.0)
    assert xplane.module_median_ms(obs, None, None, module="absent") is None
    b = xplane.breakdown(obs)
    assert b["device_ops"][0] == ["fusion", 0.15]
    assert ["bench:tick", 0.05] in b["idle_gaps"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_self_time_goes_to_the_innermost_operation():
    raw = [("%fusion.1 = f32[8] fusion(%p), kind=kOutput, calls=%c", 0, 100),
           ("%while.3 = (s32[]) while(%t), body=%b", 150, 100),
           ("%fusion.2 = f32[8] fusion(%p), kind=kLoop, calls=%d", 160, 50),
           ("%all-reduce.3 = f32[8] all-reduce(%x)", 300, 20)]
    assert xplane.short(raw[0][0]) == "fusion.1"
    assert xplane.self_time_by_family(raw) == {
        "fusion:Output": 100, "while": 50, "fusion:Loop": 50,
        "all-reduce": 20}


def test_nothing_to_read_returns_nothing():
    obs = obs_with(None)
    assert xplane.idle_share(obs, None, None) is None
    assert xplane.busy_ms_per_step(obs, None, None) is None
    assert xplane.breakdown(obs) is None


def test_span_and_counter_reducers():
    obs = obs_with(None)
    assert spans.mean_ms(obs, None, None, span="dispatch") == pytest.approx(3.0)
    assert spans.mean_ms(obs, None, None, span="absent") is None
    assert spans.share_of_window(obs, None, None, span="prefill") \
        == pytest.approx(12.5)
    assert counters.value(obs, None, None, key="compile_s") == 1.5
    assert counters.ratio_pct(obs, None, None, num="decode_stream_ticks",
                              den="decode_ticks", den_scale="slots") == 75.0


def test_parse_reads_a_recorded_trace(tmp_path):
    """``parse`` on a trace recorded here: the CPU has no device plane, but
    the harness's own annotation is found on the host's."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:tick"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    t = xplane.parse(path)
    assert t["devices"] == {}
    assert any(n == "bench:tick" for n, _s, _d in t["host"])
