"""The reduction from a trace to busy, idle, lookup and collective times."""
from pathlib import Path

from jax.profiler import ProfileData

from bench import trace

US = 1_000_000  # picoseconds


def _ev(meta, start_us, dur_us, path=None):
    stat = f' stats {{ metadata_id: 9 str_value: "{path}" }}' if path else ""
    return f"events {{ metadata_id: {meta} offset_ps: {start_us * US} duration_ps: {dur_us * US}{stat} }}"


def _device(chip, events):
    return f'''planes {{
  id: {chip + 1}
  name: "/device:TPU:{chip}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(events)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_ev(4, 0, 9000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%closed_call.7 = f32[4,16]{{1,0}} custom-call(s32[4]{{0}} %p), custom_call_target=\\"tpu_custom_call\\"" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.3 = f32[8]{{0}} fusion(f32[8,16]{{1,0}} %a), kind=kOutput" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%all-gather-start.1 = f32[16]{{0}} all-gather-start(f32[4]{{0}} %b)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit_infer" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
}}'''


LOOKUP = "jit(infer)/shard_map/multi_embedding_bag_ragged/pallas_call"
# the compiled module's text: instruction names with their op-name paths
HLO = f'''
ENTRY %main {{
  %closed_call.7 = f32[4,16]{{1,0}} custom-call(s32[4]{{0}} %p), custom_call_target="tpu_custom_call", metadata={{op_name="{LOOKUP}"}}
  ROOT %fusion.3 = f32[8]{{0}} fusion(f32[8,16]{{1,0}} %a), kind=kOutput, metadata={{op_name="jit(infer)/dot_general"}}
}}
'''
# two chips; the harness's host spans cover 0..10 ms on the trace's clock
TEXT = "\n".join([
    _device(0, [_ev(1, 1000, 4000), _ev(2, 5000, 1000), _ev(3, 6500, 500)]),
    _device(1, [_ev(1, 1000, 2000), _ev(2, 3000, 2000), _ev(3, 6500, 1500)]),
    '''planes {
  id: 9
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0 '''
    + " ".join([_ev(1, 0, 1000), _ev(2, 1000, 6000), _ev(3, 7000, 3000), _ev(4, 500, 200)])
    + ''' }
  event_metadata { key: 1 value { id: 1 name: "bench.submit" } }
  event_metadata { key: 2 value { id: 2 name: "bench.pump" } }
  event_metadata { key: 3 value { id: 3 name: "bench.collect" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(infer)" } }
}''',
])


def test_reduction_of_a_known_trace():
    r = trace.reduce_profile(ProfileData.from_text_proto(TEXT), n_chips=2, hlo_text=HLO)
    assert (r.lo, r.hi) == (0.0, 10_000_000.0)  # the harness's spans, in ns
    assert r.window_s == 0.01
    # chip 0 busy 4 + 1 + 0.5 ms, chip 1 busy 2 + 2 + 1.5 ms: 5.5 ms each
    assert r.busy_ns(0) == r.busy_ns(1) == 5_500_000
    assert abs(r.busy_s - 0.0055) < 1e-12
    assert r.busy_ns(0, "lookup") == 4_000_000 and r.busy_ns(1, "lookup") == 2_000_000
    assert r.busy_ns(0, "collective") == 500_000 and r.busy_ns(1, "collective") == 1_500_000
    # device time inside submit (0..1 ms) and pump (1..7 ms): some chip busy 1..6, 6.5..8 ms
    assert r.busy_within_ns(r.spans_named("bench.submit") + r.spans_named("bench.pump")) == 5_500_000
    b = r.breakdown()
    assert b["device_ops"][0] == [f"lookup:closed_call.7 {LOOKUP}", 0.006]
    assert trace.reduce_profile(ProfileData.from_text_proto(TEXT), n_chips=2).busy_ns(
        0, "lookup") == 0  # without the module's op names nothing reads as a lookup
    gaps = dict((round(s, 9), n) for n, s in b["idle_gaps"])
    # no chip runs 8..10 ms (collect), 0..1 ms (submit), 6..6.5 ms (pump)
    assert gaps == {0.002: "bench.collect", 0.001: "bench.submit", 0.0005: "bench.pump"}


def test_union_and_gaps():
    assert trace.union_ns([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert trace.union_ns([(0, 2), (1, 3), (5, 6)], 2.5, 5.5) == 1
    assert trace.gaps_ns([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_reduction_of_a_recorded_chip_trace():
    """Four batches of kuairec.zipf.sat traced on one TPU v5e, cut to the
    device's op line and the harness's spans; the op names of its compiled
    step beside it."""
    data = Path(__file__).with_name("data")
    r = trace.reduce_profile(
        ProfileData.from_file(str(data / "kuairec_trace.xplane.pb")), n_chips=1,
        hlo_text=(data / "kuairec_step_ops.txt").read_text())
    assert len(r.spans_named("bench.pump")) == 4 and len(r.ops) == 408
    assert (r.lo, r.hi) == (38_162_549.0, 1_057_307_724.0)
    assert r.busy_ns(0) == 3_938_504.0  # 0.39% of the window: idle 99.6%
    kernel = sum(o.end - o.start for o in r.ops if o.name == "closed_call.4")
    assert kernel == 2_996_358.0
    assert r.busy_ns(0, "lookup") == 3_035_619.0  # the kernel and its while loop
    assert r.busy_ns(0, "collective") == 0.0
    b = r.breakdown()
    assert b["device_ops"][0][0].startswith("lookup:while.3 ")
    assert b["idle_gaps"][0][0] == "bench.pump"
