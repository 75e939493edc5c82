"""The reduction of a traced window by layer (`bench.layer_trace`) and the
per-layer readers built on it: on synthetic events, on a profile written
from a text protobuf, and on spans recorded on the CPU."""
import pytest

import jax
from jax.profiler import ProfileData

from bench import layer_trace, run, spans, trace
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
COUNTED = ("useful_shard_share", "idle_tick_share")


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
    return {"window_s": layers.window_s, "trace": layers,
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


def test_device_idle_reads_what_it_read():
    t = _layered()
    layers = layer_trace.reduce(t, NAMES)
    plain = trace.reduce(_trace())
    assert device_idle.read({"trace": plain}) == pytest.approx(45.0)
    assert 100 * layers.idle_share == pytest.approx(45.0)
    assert layers.window_s == plain.window_s


def test_counters_read_the_window():
    # one spot chunk: 7 of 8 ticks run, 3 with all four workers, 4 with two
    counters = {"engine.ticks": 8, "engine.running_ticks": 7,
                "engine.shard_slots": 32, "engine.active_shards": 20}
    record = {"window_s": 1.0, "trace": None, "counters": counters}
    assert _read("useful_shard_share", record) == 62.5
    assert _read("idle_tick_share", record) == 12.5


@pytest.mark.parametrize("name", SCOPED + COUNTED)
def test_readers_find_nothing_without_the_program_s_marks(name):
    untraced = {"window_s": 1.0, "trace": None, "counters": None}
    assert _read(name, untraced) is None
    # the harness's own reduction, which keeps no scopes
    assert _read(name, {"window_s": 1.0, "trace": trace.reduce(_trace()),
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
