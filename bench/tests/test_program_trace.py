"""The program's own spans and name scopes, read from a trace: a synthetic
trace with ``repro.*`` spans nested inside ``bench.pump`` leaves every
number ``bench/trace.py`` gives as it was, and the readers of
``bench/program_trace.py`` find the spans, the scopes and the stage each
idle gap falls in."""
import types

import pytest
from jax.profiler import ProfileData

from bench import harness, program_trace, trace

US = 1_000_000  # picoseconds
LOOKUP = "jit(infer)/jit(multi_embedding_bag_ragged)/closed_call/pallas_call"
# instruction -> (HLO text of the op, its op-name path)
OPS = {
    1: ("%closed_call.7 = f32[4,16]{1,0} custom-call(s32[4]{0} %p)", LOOKUP),
    2: ("%fusion.3 = f32[8]{0} fusion(f32[8,16]{1,0} %a), kind=kOutput", "jit(infer)/tower/dot_general"),
    3: ("%fusion.5 = s32[8]{0} fusion(s32[8]{0} %i), kind=kLoop", "jit(infer)/lookup_prep/jit(_take)/gather"),
    4: ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop", "jit(infer)/scatter-add"),
}
HLO = "ENTRY %main {\n" + "\n".join(
    f'  {text}, metadata={{op_name="{path}"}}' for text, path in OPS.values()) + "\n}\n"
HARNESS = {"bench.submit": (0, 1000), "bench.pump": (1000, 9000), "bench.collect": (9000, 10000)}
PROGRAM = {  # one served batch inside bench.pump, microseconds
    "repro.validate": (1100, 3000),
    "repro.step": (3000, 7000),
    "repro.stage": (3000, 3500),
    "repro.dispatch": (3500, 4000),
    "repro.wait": (4000, 6500),
    "repro.fetch": (6500, 7000),
    "repro.complete": (7000, 8800),
}
# (op, start, end) per chip: chip 0 the busier
DEVICE = [
    [(3, 3800, 4000), (1, 4000, 5500), (2, 5500, 5800), (4, 5800, 6000), (4, 9200, 9300)],
    [(3, 3900, 4000), (2, 5500, 5700)],
]


def _ev(meta, start_us, end_us):
    return f"events {{ metadata_id: {meta} offset_ps: {start_us * US} duration_ps: {(end_us - start_us) * US} }}"


def _text(spans):
    planes = []
    for chip, ops in enumerate(DEVICE):
        meta = " ".join(
            f'event_metadata {{ key: {k} value {{ id: {k} name: "{t}" }} }}' for k, (t, _) in OPS.items())
        planes.append(f'''planes {{
  id: {chip + 1}
  name: "/device:TPU:{chip}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(_ev(*o) for o in ops)} }}
  {meta}
}}''')
    names = list(spans)
    events = " ".join(_ev(i + 1, *spans[n]) for i, n in enumerate(names))
    meta = " ".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}'
                    for i, n in enumerate(names))
    planes.append(f'''planes {{
  id: 9
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {events} }}
  {meta}
}}''')
    return "\n".join(planes)


def _profile(spans):
    return ProfileData.from_text_proto(_text(spans))


@pytest.fixture(scope="module")
def both():
    """The trace reduced with and without the program's spans in it."""
    with_program = _profile({**HARNESS, **PROGRAM})
    return (trace.reduce_profile(_profile(HARNESS), n_chips=2, hlo_text=HLO),
            trace.reduce_profile(with_program, n_chips=2, hlo_text=HLO),
            program_trace.program_spans(with_program))


def _ctx(r):
    return types.SimpleNamespace(trace=r, traced=[{"distinct": []}])


def test_program_spans_leave_the_harness_numbers_as_they_were(both):
    bare, r, _ = both
    assert (r.lo, r.hi) == (bare.lo, bare.hi) == (0.0, 10_000_000.0)
    assert r.spans == bare.spans
    assert r.spans_named("bench.pump") == [(1_000_000.0, 9_000_000.0)]
    assert r.busy_ns(0) == bare.busy_ns(0) == 2_300_000
    host = harness.load_module(harness.BENCH / "metrics" / "host_ms.py")
    # submit + pump 9 ms, a chip busy 3.8..6 ms of it
    assert host.read(_ctx(r)) == host.read(_ctx(bare)) == pytest.approx(6.8)
    assert r.breakdown() == bare.breakdown()


def test_program_spans_are_collected(both):
    _, _, spans = both
    assert {n for n, _, _ in spans} == set(PROGRAM)
    assert all(n.startswith("repro.") for n, _, _ in spans)
    assert [s for s in spans if s[0] == "repro.wait"] == [("repro.wait", 4_000_000, 6_500_000)]


@pytest.mark.parametrize("name, ms", [
    ("validate", 1.9), ("stage", 0.5), ("dispatch", 0.5), ("wait", 2.5), ("fetch", 0.5),
    ("complete", 1.8), ("step", 4.0),
])
def test_each_host_span_reads_its_wall_per_batch(both, name, ms):
    _, _, spans = both
    assert program_trace.span_ms(spans, f"repro.{name}", 1) == pytest.approx(ms)
    assert program_trace.span_ms(spans, f"repro.{name}", 2) == pytest.approx(ms / 2)


def test_host_stages_fit_inside_the_pump(both):
    _, r, spans = both
    pump_ms = sum(e - s for s, e in r.spans_named("bench.pump")) * 1e-6
    stages = ("validate", "stage", "dispatch", "fetch", "complete")
    assert sum(program_trace.span_ms(spans, f"repro.{k}", 1) for k in stages) <= pump_ms


@pytest.mark.parametrize("name, ms", [("tower_ms", 0.3), ("lookup_prep_ms", 0.2)])
def test_each_scope_metric_reads_the_busiest_chip(both, name, ms):
    bare, r, _ = both
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read
    assert read(_ctx(r)) == pytest.approx(ms)
    # the lookup kernel and the unscoped fusion count in neither
    assert read(types.SimpleNamespace(trace=r, traced=[{}, {}])) == pytest.approx(ms / 2)


@pytest.mark.parametrize("name", ["tower_ms", "lookup_prep_ms"])
def test_scope_metrics_read_nothing_without_the_scopes(name):
    """A program without the name scopes (the recorded chip trace's): no number."""
    from pathlib import Path

    data = Path(__file__).with_name("data")
    r = trace.reduce_profile(
        ProfileData.from_file(str(data / "kuairec_trace.xplane.pb")), n_chips=1,
        hlo_text=(data / "kuairec_step_ops.txt").read_text())
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read
    assert read(types.SimpleNamespace(trace=r, traced=[{}] * 4)) is None
    assert read(types.SimpleNamespace(trace=None, traced=[])) is None


def test_idle_gaps_are_named_by_the_innermost_span(both):
    bare, r, spans = both
    # no chip runs 0..3.8 ms, 6..9.2 ms, 9.3..10 ms
    want = {0.0038: "repro.validate", 0.0032: "repro.complete", 0.0007: "bench.collect"}
    got = {round(s, 9): n for n, s in program_trace.idle_gaps(r, spans)}
    assert got == want
    # without the program's spans the same gaps read as the harness's
    assert {round(s, 9): n for n, s in program_trace.idle_gaps(bare, [])} == {
        0.0038: "bench.pump", 0.0032: "bench.pump", 0.0007: "bench.collect"}
