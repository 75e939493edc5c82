"""The reduction from profiler trace to busy time, idle gaps and their
labels: on synthetic events, and on a small trace recorded on the CPU."""
import pytest

import jax
import jax.numpy as jnp

from bench import spans, trace

MS = 1e6   # ns


def _trace():
    # window: the chunk spans, 0..100 ms; inside the first chunk a batch
    # span 10..20 ms
    host = [("bench.chunk", 0 * MS, 50 * MS), ("bench.batch", 10 * MS, 20 * MS),
            ("bench.chunk", 60 * MS, 100 * MS), ("bench.init", -50 * MS, -1)]
    ops = [("fusion.1", 20 * MS, 40 * MS), ("fusion.2", 40 * MS, 45 * MS),
           ("fusion.1", 70 * MS, 95 * MS), ("copy", -10 * MS, 5 * MS)]
    return trace.Trace({"/device:TPU:0": ops}, host)


def test_busy_is_the_union_of_op_intervals():
    r = trace.reduce(_trace())
    assert r.window_s == pytest.approx(0.1)
    # 0-5 (clipped copy), 20-45, 70-95
    assert r.busy_s == pytest.approx(0.055)
    assert r.idle_share == pytest.approx(0.45)


def test_top_ops_sum_device_time_by_name():
    r = trace.reduce(_trace())
    assert r.device_ops[0][0] == "fusion.1"
    assert r.device_ops[0][1] == pytest.approx(0.045)
    assert dict(r.device_ops)["copy"] == pytest.approx(0.005)


def test_idle_gaps_carry_the_open_span():
    r = trace.reduce(_trace())
    got = sorted((round(s, 4), name) for name, s in r.idle_gaps)
    # 5-20 (mid 12.5: batch inside chunk), 45-70 (mid 57.5: no span),
    # 95-100 (chunk)
    assert got == [(0.005, "bench.chunk"), (0.015, "bench.batch"),
                   (0.025, "host")]


def test_busy_averages_over_the_chips_that_ran():
    t = _trace()
    t.devices["/device:TPU:1"] = [("fusion.9", 0, 100 * MS)]
    t.devices["/device:TPU:2"] = []
    assert trace.reduce(t).busy_s == pytest.approx((0.055 + 0.1) / 2)


def test_a_window_without_device_work_is_refused():
    t = _trace()
    t.devices = {"/device:TPU:0": []}
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_union_and_gaps_edge_cases():
    assert trace.union([(0, 1), (1, 2), (5, 4)], 0, 10) == [(0, 2)]
    assert trace.gaps([], 0, 3) == [(0, 3)]
    assert trace.gaps([(0, 3)], 0, 3) == []


def test_spans_recorded_on_cpu_are_read_back(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans.span(spans.CHUNK):
        with spans.span(spans.BATCH):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(trace.newest_xplane(str(tmp_path)))
    names = [s[0] for s in t.spans]
    assert names.count("bench.chunk") == 1 and names.count("bench.batch") == 1
    lo, hi = trace.window_of(t.spans)
    batch = next(s for s in t.spans if s[0] == "bench.batch")
    assert lo <= batch[1] < batch[2] <= hi
    assert trace.label(t.spans, (batch[1] + batch[2]) / 2) == "bench.batch"


def test_nested_ops_count_self_time_only():
    ops = [("%while.1 = (s32[], f32[2]) while(%t), body=%b", 0, 100),
           ("%fusion.2 = f32[2]{0:T(128)} fusion(%p), kind=kLoop", 10, 40),
           ("%copy.3 = f32[2]{0:T(8,128)S(1)} copy(%q)", 50, 60),
           ("%fusion.2 = f32[2]{0:T(128)} fusion(%p), kind=kLoop", 120, 130)]
    got = trace.self_times(ops, 0, 200)
    assert got[ops[0][0]] == 60 and got[ops[1][0]] == 40
    assert got[ops[2][0]] == 10
    t = trace.Trace({"/device:TPU:0": ops},
                    [("bench.chunk", 0, 200)])
    r = trace.reduce(t)
    assert dict(r.device_ops) == pytest.approx(
        {"%while.1 while": 60e-9, "%fusion.2 fusion": 40e-9,
         "%copy.3 copy": 10e-9})
    assert r.busy_s == pytest.approx(110e-9)
