"""The reduction of a traced window by layer (`bench.layer_trace`) and the
per-layer readers built on it: on synthetic events, on a profile written
from a text protobuf, and on spans recorded on the CPU."""
import json

import pytest

import jax
from jax.profiler import ProfileData

from bench import layer_trace, run, spans, spec, trace
from bench.layers import device_idle
from bench.tests.test_trace import MS, _trace

NAMES = {
    "fusion.1": "jit(f)/while/body/repro.step/"
                "transpose(jvp(repro.loss_head))/dot_general:",
    "fusion.2": "jit(f)/while/body/repro.step/repro.update/mul",
    "fusion.3": "jit(f)/while/body/repro.step/checkpoint/dot_general",
    "fusion.4": "jit(f)/while/body/repro.market/jit(_uniform)/xor",
}
SCOPED = ("loss_head_share", "update_share", "body_share", "market_share",
          "host_idle")
COUNTED = ("useful_shard_share", "idle_tick_share", "skipped_shard_share")


def _layered():
    """`test_trace`'s window (0..100 ms, busy 0-5, 20-45, 70-95) with the
    program's scopes on its ops and its spans on the host."""
    t = _trace()
    t.devices["/device:TPU:0"] += [("fusion.4", 30 * MS, 32 * MS),
                                   ("fusion.3", 80 * MS, 90 * MS)]
    t.spans += [("repro.engine.dispatch", 2 * MS, 8 * MS),
                ("repro.prepare.batches", 50 * MS, 65 * MS),
                ("repro.engine.fetch", 96 * MS, 100 * MS)]
    return t


def _record(layers, counters=None):
    return {"window_s": layers.window_s, "layers": layers,
            "counters": counters}


def _read(name, record):
    return run.layer_value(name, record)


def test_scope_shares_unscoped_and_idle_make_the_window():
    layers = layer_trace.reduce(_layered(), NAMES)
    # fusion.1: 20-40 less fusion.4's 2 ms, 70-95 less fusion.3's 10 ms
    assert layers.scope_s == pytest.approx({
        "repro.loss_head": 0.033, "repro.step": 0.010,
        "repro.update": 0.005, "repro.market": 0.002, "unscoped": 0.005})
    record = _record(layers)
    got = {m: _read(m, record) for m in SCOPED[:4]}
    assert got == pytest.approx({"loss_head_share": 33.0,
                                 "update_share": 5.0, "body_share": 10.0,
                                 "market_share": 2.0})
    unscoped = 100 * layers.scope_s["unscoped"] / layers.window_s
    idle = 100 * layers.idle_share
    assert sum(got.values()) + unscoped + idle == pytest.approx(100.0)


def test_program_spans_label_gaps_and_make_host_idle():
    layers = layer_trace.reduce(_layered(), NAMES)
    gaps = sorted((round(s, 4), name) for name, s in layers.idle_gaps)
    # 5-20 (mid 12.5, the batch span), 45-70 (mid 57.5, preparing the
    # feed), 95-100 (fetching)
    assert gaps == [(0.005, "repro.engine.fetch"), (0.015, "bench.batch"),
                    (0.025, "repro.prepare.batches")]
    # idle under a program span: 5-8, 50-65, 96-100
    assert layers.host_idle_s == pytest.approx(0.022)
    assert _read("host_idle", _record(layers)) == pytest.approx(22.0)


def test_top_ops_carry_their_scope():
    layers = layer_trace.reduce(_layered(), NAMES)
    ops = dict(layers.device_ops)
    assert ops["fusion.1 [repro.loss_head]"] == pytest.approx(0.033)
    assert ops["fusion.3 [repro.step]"] == pytest.approx(0.010)
    assert ops["copy"] == pytest.approx(0.005)
    assert layers.unscoped_ops == [["copy", pytest.approx(0.005)]]


LOOP = "%while.7 = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %p), " \
       "condition=%c, body=%b"


def _looped(dropped_ms: float):
    """A window (0..100 ms) of two runs of one tick loop, 0-40 and 50-90:
    the first with its body (30 ms of ``repro.step``, 10 of
    ``repro.loss_head``), the second with the profile holding only its
    first 10 ms of body and dropping the next ``dropped_ms``."""
    t = trace.Trace({"/device:TPU:0": [
        (LOOP, 0, 40 * MS), ("fusion.3", 0, 30 * MS),
        ("fusion.1", 30 * MS, 40 * MS),
        (LOOP, 50 * MS, (60 + dropped_ms) * MS),
        ("fusion.3", 50 * MS, 60 * MS)]},
        [("bench.chunk", 0, 100 * MS)])
    return layer_trace.reduce(t, dict(NAMES, **{LOOP: "jit(f)/while"}))


def test_a_loop_holding_much_time_is_a_hole_the_shares_leave_out():
    layers = _looped(30)
    assert layers.lost_s == pytest.approx(0.030)
    assert "unscoped" not in layers.scope_s
    # 50 ms of the 80 busy kept their ops: each scope scaled by 80 / 50
    assert layers.scope_s == pytest.approx({"repro.step": 0.064,
                                            "repro.loss_head": 0.016})
    record = _record(layers)
    assert _read("body_share", record) == pytest.approx(64.0)
    assert _read("loss_head_share", record) == pytest.approx(16.0)
    assert 100 * layers.idle_share == pytest.approx(20.0)
    # the top ops still name the loop and its time
    assert dict(layers.device_ops)["%while.7 while"] == pytest.approx(0.030)


def test_a_loop_s_own_small_time_is_no_hole():
    # 0.5 ms of the loop's own, under 1 % of the window
    layers = _looped(0.5)
    assert layers.lost_s == 0.0
    assert layers.scope_s == pytest.approx({
        "repro.step": 0.040, "repro.loss_head": 0.010, "unscoped": 0.0005})


def test_device_idle_reads_what_it_read():
    t = _layered()
    layers = layer_trace.reduce(t, NAMES)
    plain = trace.reduce(_trace())
    assert device_idle.read({"trace": plain}) == pytest.approx(45.0)
    assert 100 * layers.idle_share == pytest.approx(45.0)
    assert layers.window_s == plain.window_s


def test_counters_read_the_window():
    # one spot chunk: 7 of 8 ticks run, 3 with all four workers, 4 with two
    # and the step skips the 12 slots of the preempted and idle shards
    counters = {"engine.ticks": 8, "engine.running_ticks": 7,
                "engine.shard_slots": 32, "engine.active_shards": 20,
                "engine.skipped_shards": 12}
    record = {"window_s": 1.0, "trace": None, "layers": None,
              "counters": counters}
    assert _read("useful_shard_share", record) == 62.5
    assert _read("idle_tick_share", record) == 12.5
    assert _read("skipped_shard_share", record) == 37.5


@pytest.mark.parametrize("name", SCOPED + COUNTED)
def test_readers_find_nothing_without_the_program_s_marks(name):
    untraced = {"window_s": 1.0, "trace": None, "layers": None,
                "counters": None}
    assert _read(name, untraced) is None
    # the harness's own reduction, which keeps no scopes or host idle
    plain = trace.reduce(_trace())
    assert _read(name, {"window_s": 1.0, "trace": plain, "layers": plain,
                        "counters": {}}) is None
    # a program that predates the scopes, spans and counters
    bare = layer_trace.reduce(_trace(), {})
    assert bare.scope_s is None and bare.host_idle_s is None
    assert _read(name, _record(bare, {})) is None


def test_scope_of_takes_the_innermost_known_segment():
    assert layer_trace.scope_of(NAMES["fusion.1"]) == "repro.loss_head"
    assert layer_trace.scope_of(NAMES["fusion.3"]) == "repro.step"
    assert layer_trace.scope_of(
        "jit(g)/repro.step/jvp(repro.gate)/repro.stepper/add") == \
        "repro.gate"
    assert layer_trace.scope_of("jit(g)/while/body/add") is None
    assert layer_trace.scope_of("") is None


def _declares(monkeypatch, names):
    """The program's `repro.obs` as one that records the scopes it opens
    and has opened ``names``."""
    from repro import obs

    monkeypatch.setattr(obs, "scope_names", lambda: frozenset(names),
                        raising=False)


def test_scope_of_takes_a_scope_the_program_declares(monkeypatch):
    path = "jit(g)/repro.step/transpose(jvp(repro.moe.route))/dot_general:"
    _declares(monkeypatch, {"repro.moe.route", "scratch_scope"})
    assert layer_trace.scope_of(path) == "repro.moe.route"
    assert layer_trace.scope_of("jit(g)/repro.step/scratch_scope/add") == \
        "scratch_scope"
    # a name the program never opened is still no scope
    assert layer_trace.scope_of(
        "jit(g)/repro.step/jvp(repro.gate)/repro.stepper/add") == \
        "repro.gate"
    layers = layer_trace.reduce(_layered(), dict(NAMES, **{
        "fusion.3": "jit(f)/while/body/repro.step/scratch_scope/mul"}))
    assert layers.scope_s["scratch_scope"] == pytest.approx(0.010)
    assert "repro.step" not in layers.scope_s


def test_without_declared_scopes_the_six_alone_apply(monkeypatch):
    from repro import obs

    monkeypatch.delattr(obs, "scope_names", raising=False)
    assert layer_trace.known_scopes() == frozenset(layer_trace.SCOPES)
    assert layer_trace.scope_of(
        "jit(g)/repro.step/transpose(jvp(repro.moe.route))/dot") == \
        "repro.step"


XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata {
    key: 1
    value {
      id: 1
      name: "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop"
      stats { metadata_id: 7 str_value: "jit(f)/repro.step/dot_general:" }
    }
  }
  event_metadata {
    key: 2
    value {
      id: 2
      name: "%fusion.2 = f32[2]{0} fusion(f32[2]{0} %q), kind=kLoop"
      stats { metadata_id: 8 str_value: "f32[2]{0}" }
      stats { metadata_id: 7 ref_value: 9 }
    }
  }
  event_metadata {
    key: 3
    value { id: 3 name: "%copy.3 = f32[2]{0} copy(f32[2]{0} %r)" }
  }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "shape_with_layout" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(f)/repro.update/add" } }
}
planes {
  id: 2
  name: "/host:CPU"
  event_metadata {
    key: 1
    value {
      id: 1
      name: "host op"
      stats { metadata_id: 1 str_value: "jit(f)/repro.market/add" }
    }
  }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""


def test_op_names_come_from_the_device_planes_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    names = layer_trace.op_names(str(path))
    ops = trace.load(str(path)).devices["/device:TPU:0"]
    fusion1, fusion2, copy = (name for name, _, _ in ops)
    assert names == {fusion1: "jit(f)/repro.step/dot_general:",
                     fusion2: "jit(f)/repro.update/add"}
    assert copy not in names


def test_program_spans_recorded_on_cpu_are_read_back(tmp_path):
    from repro import obs

    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans.span(spans.CHUNK):
        with obs.span("repro.engine.dispatch"):
            out = f(x)
        with obs.span("repro.engine.fetch"):
            out.block_until_ready()
    jax.profiler.stop_trace()
    path = trace.newest_xplane(str(tmp_path))
    got = layer_trace.program_spans(path)
    assert [s[0] for s in got] == ["repro.engine.dispatch",
                                   "repro.engine.fetch"]
    lo, hi = trace.window_of(trace.load(path).spans)
    assert all(lo <= a <= b <= hi for _, a, b in got)


def _event(meta: int, start_ms: float, end_ms: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * 1e9)}"
            f" duration_ps: {int((end_ms - start_ms) * 1e9)} }}")


def _meta(key: int, name: str, op_name: str = None) -> str:
    stat = (f' stats {{ metadata_id: 99 str_value: "{op_name}" }}'
            if op_name else "")
    return (f'event_metadata {{ key: {key} value {{ id: {key} '
            f'name: "{name}"{stat} }} }}')


def _profile(tmp_path) -> str:
    """A traced window as the profiler writes it: one chunk span of 100 ms
    on the host with the program's spans inside it, and on the chip an op
    under each scope and one under none."""
    ops = [("%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
            "jit(f)/while/body/repro.step/dot_general:", 0, 40),
           ("%fusion.2 = f32[2]{0} fusion(f32[2]{0} %p), kind=kOutput",
            "jit(f)/while/body/repro.step/transpose(jvp(repro.loss_head))"
            "/dot_general:", 40, 60),
           ("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
            "jit(f)/while/body/repro.step/repro.update/mul", 60, 70),
           ("%fusion.4 = u32[2]{0} fusion(u32[2]{0} %p), kind=kLoop",
            "jit(f)/while/body/repro.market/xor", 70, 72),
           ("%copy.5 = f32[2]{0} copy(f32[2]{0} %r)", None, 72, 80)]
    host = [("bench.chunk", 0, 100), ("repro.engine.dispatch", 1, 3),
            ("repro.engine.fetch", 85, 100)]
    device = "\n".join(
        [f'lines {{ id: 1 name: "XLA Ops" '
         + " ".join(_event(i + 1, a, b) for i, (_, _, a, b) in
                    enumerate(ops)) + " }"]
        + [_meta(i + 1, name, op) for i, (name, op, _, _) in enumerate(ops)]
        + ['stat_metadata { key: 99 value { id: 99 name: "tf_op" } }'])
    cpu = "\n".join(
        [f'lines {{ id: 1 name: "python" '
         + " ".join(_event(i + 1, a, b) for i, (_, a, b) in
                    enumerate(host)) + " }"]
        + [_meta(i + 1, name) for i, (name, _, _) in enumerate(host)])
    text = (f'planes {{ id: 1 name: "/device:TPU:0" {device} }}\n'
            f'planes {{ id: 2 name: "/host:CPU" {cpu} }}')
    path = tmp_path / "window.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_traced_record_gives_every_per_layer_metric_a_value(tmp_path):
    """`bench.run`'s record of a traced window, built from a profile and
    the window's counters, gives each reader ``BENCHMARK.json`` lists a
    value in every cell; the harness's reduction in it is
    `bench.trace.reduce`'s, as the line's breakdown and device idle
    share read it."""
    path = _profile(tmp_path)
    record = {"window_s": 0.1, "chips": 1, "useful_flops": 1e12,
              "peak_flops": 1.97e14, "compile_s": 3.0}
    counters = {"engine.ticks": 8, "engine.running_ticks": 7,
                "engine.shard_slots": 32, "engine.active_shards": 20,
                "engine.skipped_shards": 12}
    run.read_trace(record, path, counters)
    benchmark = json.load(open(f"{spec.ROOT}/BENCHMARK.json"))
    for cell in benchmark["workloads"]:
        for m in spec.metrics_of(benchmark["per_layer"], cell["name"]):
            assert _read(m["name"], record) is not None, (cell, m)
    plain = trace.reduce(trace.load(path))
    assert record["trace"] == plain
    assert _read("device_idle", record) == pytest.approx(20.0)
    got = {m: _read(m, record) for m in SCOPED[:4]}
    assert got == pytest.approx({"body_share": 40.0,
                                 "loss_head_share": 20.0,
                                 "update_share": 10.0, "market_share": 2.0})
    unscoped = 100 * record["layers"].scope_s["unscoped"] / 0.1
    assert sum(got.values()) + unscoped + 20.0 == pytest.approx(100.0)
    # idle 80-100 ms, under the fetch span from 85 ms
    assert _read("host_idle", record) == pytest.approx(15.0)
    assert _read("skipped_shard_share", record) == 37.5
