"""Input-hardening tests (DESIGN.md §9): the validation policy registry,
the three OOV/negative-index modes, and the server-side wiring.

The hard guarantee under test: ``clip`` is today's behavior made explicit —
bit-identical outputs on every execution path, it only *counts*.
``null-row`` maps invalid ids onto the ``-1`` padding sentinel (exact zeros
in every path); ``reject`` fails only the offending requests' handles with
a typed :class:`InvalidQueryError` while the rest of the batch serves.
"""
import numpy as np
import pytest

from repro.data.distributions import Zipf, sample_workload
from repro.data.workloads import small_workload
from repro.serving.validation import (
    VALIDATION_MODES,
    IndexValidator,
    payload_validator,
)


# ------------------------------------------------------------ IndexValidator


def test_modes_registry_matches_engine():
    from repro.engine import VALIDATION_POLICIES

    assert set(VALIDATION_MODES) <= set(VALIDATION_POLICIES.names())


def test_clip_is_pass_through():
    v = IndexValidator([10, 20], "clip")
    idx = np.array([[3, 99, -1], [-7, 19, 5]], np.int32)
    out, counts = v.check(idx)
    assert out is idx  # not even copied
    assert counts == {"oov": 1, "negative": 1, "invalid": 2}


def test_null_row_maps_invalid_to_padding_sentinel():
    v = IndexValidator([10, 20], "null-row")
    idx = np.array([[3, 99, -1], [-7, 19, 5]], np.int32)
    out, counts = v.check(idx)
    assert out.tolist() == [[3, -1, -1], [-1, 19, 5]]
    assert out.dtype == idx.dtype
    assert counts["invalid"] == 2
    # the original is untouched
    assert idx[0, 1] == 99


def test_padding_sentinel_is_never_invalid():
    v = IndexValidator([10], "reject")
    out, counts = v.check(np.array([[-1, -1, 0]], np.int32))
    assert counts == {"oov": 0, "negative": 0, "invalid": 0}
    assert out.tolist() == [[-1, -1, 0]]


def test_empty_batch_counts_zero():
    v = IndexValidator([10, 20], "null-row")
    out, counts = v.check(np.zeros((2, 0), np.int32))
    assert out.shape == (2, 0)
    assert counts == {"oov": 0, "negative": 0, "invalid": 0}


def test_all_oov_batch():
    v = IndexValidator([4], "null-row")
    out, counts = v.check(np.array([[4, 5, 6, 7]], np.int32))
    assert counts["oov"] == 4 and counts["invalid"] == 4
    assert (out == -1).all()


def test_table_count_mismatch_raises():
    v = IndexValidator([10, 20], "clip")
    with pytest.raises(ValueError):
        v.check(np.zeros((3, 2), np.int32))


def test_unknown_mode_raises():
    with pytest.raises(ValueError):
        IndexValidator([10], "bogus")


# ------------------------------------------------------------ payload_validator


def test_payload_validator_reject_flags_only_bad_positions():
    validate = payload_validator([10, 20], "reject")
    good = np.array([[1], [2]], np.int32)
    bad = np.array([[99], [2]], np.int32)
    out, counts, flagged = validate([good, bad, good])
    assert list(flagged) == [1]
    assert "out-of-vocab" in flagged[1] or "invalid" in flagged[1]
    assert counts["oov"] == 1
    # surviving payloads pass through unmodified
    assert np.array_equal(out[0], good) and np.array_equal(out[2], good)


def test_payload_validator_mapping_payloads():
    validate = payload_validator([10], "null-row")
    out, counts, flagged = validate([{"indices": np.array([[99]], np.int32)}])
    assert counts["oov"] == 1 and not flagged
    assert out[0]["indices"].tolist() == [[-1]]


def _reference_validator(rows, mode):
    """The per-query reference: one :meth:`IndexValidator.check` per payload,
    the loop the batch validator replaces."""
    v = IndexValidator(rows, mode)

    def validate(payloads):
        counts = {"oov": 0, "negative": 0}
        bad = {}
        out = list(payloads)
        for i, p in enumerate(payloads):
            sanitized, c = v.check(p["indices"] if isinstance(p, dict) else p)
            counts["oov"] += c["oov"]
            counts["negative"] += c["negative"]
            if not c["invalid"]:
                continue
            if mode == "reject":
                bad[i] = (
                    f"{c['oov']} out-of-vocab + {c['negative']} negative "
                    f"indices in query"
                )
            elif mode == "null-row":
                out[i] = (
                    dict(p, indices=sanitized) if isinstance(p, dict) else sanitized
                )
        return out, counts, bad

    validate.mode = mode
    return validate


_ROWS = [10, 50, 7, 1000]


def _planted_batch(rng, n, seq, form, shapes=None):
    """``n`` payloads of ``(T, seq)`` int32 ids with planted OOV ids, ids
    below -1 and -1 padding; ``shapes`` overrides the per-query seq."""
    payloads = []
    for q in range(n):
        s = seq if shapes is None else shapes[q % len(shapes)]
        idx = np.stack([rng.integers(0, r, s) for r in _ROWS]).astype(np.int32)
        roll = rng.random(idx.shape)
        idx[roll < 0.05] = -1
        oov = roll > 0.985
        idx[oov] = (np.array(_ROWS)[:, None] + rng.integers(0, 5, idx.shape))[oov]
        idx[(roll > 0.05) & (roll < 0.065)] = -rng.integers(2, 9)
        payloads.append(
            {"dense": rng.normal(size=3), "indices": idx} if form == "mapping" else idx
        )
    return payloads


def _assert_same_outcome(got, want, payloads):
    out, counts, bad = got
    ref_out, ref_counts, ref_bad = want
    assert counts == ref_counts
    assert bad == ref_bad
    assert len(out) == len(ref_out) == len(payloads)
    for o, r, p in zip(out, ref_out, payloads):
        if r is p:
            assert o is p  # unflagged payloads pass through as the same object
            continue
        if isinstance(r, dict):
            assert o.keys() == r.keys() and o["dense"] is p["dense"]
            o, r = o["indices"], r["indices"]
        assert o.dtype == r.dtype and np.array_equal(o, r)


@pytest.mark.parametrize("seq", [1, 3])
@pytest.mark.parametrize("form", ["array", "mapping"])
@pytest.mark.parametrize("mode", VALIDATION_MODES)
def test_batch_validator_matches_per_query_check(mode, form, seq):
    rng = np.random.default_rng([seq, len(form), len(mode)])
    payloads = _planted_batch(rng, 300, seq, form)
    originals = [
        (p["indices"] if form == "mapping" else p).copy() for p in payloads
    ]
    got = payload_validator(_ROWS, mode)(payloads)
    want = _reference_validator(_ROWS, mode)(payloads)
    assert want[1]["oov"] and want[1]["negative"]  # the planted ids are there
    if mode != "clip":
        assert len(want[2]) + sum(o is not p for o, p in zip(want[0], payloads))
    _assert_same_outcome(got, want, payloads)
    for p, orig in zip(payloads, originals):  # inputs never written to
        assert np.array_equal(p["indices"] if form == "mapping" else p, orig)


@pytest.mark.parametrize("form", ["array", "mapping"])
def test_clip_returns_the_same_payload_objects(form):
    payloads = _planted_batch(np.random.default_rng(3), 64, 2, form)
    before = [(p["indices"] if form == "mapping" else p).copy() for p in payloads]
    out, counts, bad = payload_validator(_ROWS, "clip")(payloads)
    assert out is payloads and not bad and counts["oov"]
    for p, b in zip(payloads, before):
        assert np.array_equal(p["indices"] if form == "mapping" else p, b)


@pytest.mark.parametrize("mode", VALIDATION_MODES)
def test_mixed_index_shapes_validate_per_shape(mode):
    """A batch mixing index shapes (and an int64 query among int32 ones)
    validates each query as the per-query check would, without raising."""
    rng = np.random.default_rng(11)
    payloads = _planted_batch(rng, 120, 1, "mapping", shapes=[1, 4])
    payloads[7] = dict(payloads[7], indices=payloads[7]["indices"].astype(np.int64))
    payloads[9] = dict(payloads[9], indices=np.zeros((len(_ROWS), 0), np.int32))
    payloads[10] = np.zeros((0,), np.int32)  # empty: no table count to check
    got = payload_validator(_ROWS, mode)(payloads)
    _assert_same_outcome(got, _reference_validator(_ROWS, mode)(payloads), payloads)


def test_batch_validator_table_count_mismatch_raises():
    validate = payload_validator([10, 20], "clip")
    with pytest.raises(ValueError):
        validate([np.zeros((2, 1), np.int32), np.zeros((3, 1), np.int32)])


# ------------------------------------------------------------ server wiring


def _traffic(wl, n_batches, batch, seed=0):
    rng = np.random.default_rng(seed)
    return [
        sample_workload(rng, wl, Zipf(1.2), batch) for _ in range(n_batches)
    ]


def _engine(validation, **overrides):
    from repro.engine import EngineConfig, InferenceEngine

    wl = small_workload("val", batch=8)
    kwargs = dict(
        planner="asymmetric", use_kernels="xla", mesh_shape=(1, 1),
        validation=validation, max_batch=8,
    )
    kwargs.update(overrides)
    return InferenceEngine.build(None, wl, EngineConfig(**kwargs)), wl


def _drive(srv, wl, batches):
    handles = []
    for idx in batches:
        handles.extend(
            srv.submit_request(idx[:, q]) for q in range(idx.shape[1])
        )
        srv.pump()
    srv.drain()
    return handles


def test_server_reject_fails_only_offending_handles():
    from repro.serving.server import InvalidQueryError

    engine, wl = _engine("reject")
    srv = engine.serve(max_wait_s=0.0)
    batches = _traffic(wl, 2, 8)
    batches[1][0, 3, 0] = wl.tables[0].rows + 7  # poison one query
    handles = _drive(srv, wl, batches)
    s = srv.stats()
    assert s["invalid"] == 1 and s["served"] == 15
    assert s["validation"]["oov_indices"] == 1
    with pytest.raises(InvalidQueryError):
        handles[8 + 3].result()
    for i, h in enumerate(handles):
        if i != 11:
            assert h.result().shape == (len(wl.tables), wl.tables[0].dim)
    # identity including the invalid term
    assert s["submitted"] == s["served"] + s["failed"] + s["invalid"]


def test_server_null_row_serves_oov_as_zeros():
    engine, wl = _engine("null-row")
    srv = engine.serve(max_wait_s=0.0)
    idx = _traffic(wl, 1, 8)[0]
    idx[2, 5, 0] = -44  # negative (not the -1 sentinel)
    handles = _drive(srv, wl, [idx])
    s = srv.stats()
    assert s["invalid"] == 0 and s["served"] == 8
    assert s["validation"]["negative_indices"] == 1
    # table 2 is seq-1: the nulled query's table-2 pooled row is exactly zero
    out = np.asarray(handles[5].result())
    assert not out[2].any()


@pytest.mark.parametrize("use_kernels,reduce_mode", [
    ("xla", "psum"),
    ("xla", "sparse"),
])
def test_clip_bit_parity_against_no_validator(use_kernels, reduce_mode):
    """clip-mode outputs are bitwise identical to a server with no
    validator at all — on clean AND on OOV-poisoned traffic."""
    engine, wl = _engine(
        "clip", use_kernels=use_kernels, reduce_mode=reduce_mode
    )
    batches = _traffic(wl, 3, 8)
    batches[1][4, 2, 0] = wl.tables[4].rows + 123  # OOV survives clip

    def results(**kw):
        srv = engine.serve(max_wait_s=0.0, **kw)
        return [np.asarray(h.result()) for h in _drive(srv, wl, batches)]

    a = results()
    b = results(validator=None)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


def test_server_stats_counters_accumulate():
    engine, wl = _engine("clip")
    srv = engine.serve(max_wait_s=0.0)
    batches = _traffic(wl, 2, 8)
    batches[0][0, 0, 0] = wl.tables[0].rows  # oov
    batches[1][1, 1, 1] = -9                 # negative
    _drive(srv, wl, batches)
    v = srv.stats()["validation"]
    assert v["mode"] == "clip"
    assert v["oov_indices"] == 1 and v["negative_indices"] == 1
    assert v["invalid_queries"] == 0  # clip never fails a request


@pytest.mark.parametrize("mode", VALIDATION_MODES)
def test_server_clean_batch_matches_per_query_validator(mode):
    """On clean traffic the batch validator hands the step the very payloads
    it was given, and the counters and every handle's result are those the
    per-query reference gives."""
    engine, wl = _engine(mode)
    batches = _traffic(wl, 2, 8)
    rows = [t.rows for t in wl.tables]

    def run(**kw):
        srv = engine.serve(max_wait_s=0.0, **kw)
        handles = _drive(srv, wl, batches)
        return srv.stats(), [np.asarray(h.result()) for h in handles]

    s_batch, r_batch = run()
    s_ref, r_ref = run(validator=_reference_validator(rows, mode))
    assert s_batch["validation"] == s_ref["validation"]
    assert s_batch["validation"]["oov_indices"] == 0
    assert s_batch["served"] == s_ref["served"] == 16
    assert s_batch["invalid"] == s_ref["invalid"] == 0
    for x, y in zip(r_batch, r_ref, strict=True):
        assert x.dtype == y.dtype and np.array_equal(x, y)

    srv = engine.serve(max_wait_s=0.0)
    for q in range(8):
        srv.submit_request(batches[0][:, q])
    released = srv.batcher.maybe_release(srv.clock(), force=True)
    payloads = [q.payload for q in released]
    assert srv._validate(released) is released
    assert all(q.payload is p for q, p in zip(released, payloads))


def test_server_validate_hands_on_rewritten_payloads():
    engine, wl = _engine("null-row")
    srv = engine.serve(max_wait_s=0.0)
    idx = _traffic(wl, 1, 8)[0]
    idx[1, 6, 0] = wl.tables[1].rows + 3
    for q in range(8):
        srv.submit_request(idx[:, q])
    released = srv.batcher.maybe_release(srv.clock(), force=True)
    payloads = [q.payload for q in released]
    live = srv._validate(released)
    assert len(live) == 8
    assert live[6].payload[1, 0] == -1 and payloads[6][1, 0] == wl.tables[1].rows + 3
    assert all(live[q].payload is payloads[q] for q in range(8) if q != 6)


def test_idle_server_percentiles_are_none():
    """Satellite regression: an idle server's latency summary used to emit
    NaN percentiles; now both the tracker and stats() surface None."""
    from repro.serving.latency import LatencyTracker
    from repro.serving.server import Server

    t = LatencyTracker()
    assert t.p50 is None and t.p99 is None
    assert t.summary()["p50_us"] is None

    srv = Server(lambda p: list(p), max_batch=4, max_wait_s=0.0)
    s = srv.stats()
    assert s["p50_us"] is None and s["p99_us"] is None
