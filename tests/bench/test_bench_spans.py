"""The reductions over the program's ``rgl.*`` spans (``bench/spans.py``)
and the queue-wait reader: a hand-built trace with known answers, a slice
of a closed-lookup trace recorded on a TPU v5 lite with the program's
spans, and the existing readers' values pinned on the older recorded
slice."""
import json
import math
import types

import pytest

from bench import harness as H
from bench import spans as sp
from bench import trace as tr
from benchutil import FIXTURES

MS = 1_000_000  # ns
DEV = "/device:TPU:0"


def _hand_trace(with_spans: bool = True):
    # window 0..100 ms; device busy 10-30, 50-60 and 90-110 (clipped to
    # 100); idle 0-10, 30-50, 60-90
    modules = [("jit_serve_step", 10 * MS, 15 * MS, DEV),
               ("jit_topk_similarity", 20 * MS, 10 * MS, DEV),
               ("jit_serve_step", 50 * MS, 10 * MS, DEV),
               ("jit__prefill_batch", 90 * MS, 20 * MS, DEV)]
    host = [("bench:window", 0, 100 * MS),
            ("bench:rag_step", 0, 100 * MS),
            ("bench:engine_step", 60 * MS, 40 * MS)]
    t = tr.Trace(modules=modules, ops=[], host=host, devices=1)
    if with_spans:
        t.spans = [(f"rgl.{name}", a * MS, (b - a) * MS, {})
                   for name, a, b in [
                       ("step", 0, 100),
                       ("decode", 2, 9),  # idle 7
                       ("admit", 28, 52),  # idle 30-50
                       ("linearize", 32, 36),
                       ("linearize", 40, 44),
                       ("prefill", 46, 54),  # idle 46-50, inside admit's
                       ("decode", 58, 93),  # idle 60-90
                       ("decode.wait", 88, 93)]]
    return t


def _run(t):
    return types.SimpleNamespace(trace=t, trace_window=tr.window(t))


def test_span_time_and_idle_inside():
    t = _hand_trace()
    lo, hi = tr.window(t)
    assert sp.span_time(t, ["rgl.linearize"], lo, hi) == \
        pytest.approx((0.008, 2))
    # exact names: the decode spans, not decode.wait
    assert sp.span_time(t, ["rgl.decode"], lo, hi) == \
        pytest.approx((0.042, 2))
    assert sp.idle_inside(t, ["rgl.decode"], lo, hi) == pytest.approx(0.037)
    # the union: 46-50 lies in both admit and prefill and counts once
    assert sp.idle_inside(t, ["rgl.admit", "rgl.prefill"], lo, hi) == \
        pytest.approx(0.020)
    assert sp.idle_inside(t, ["rgl.decode.wait"], lo, hi) == \
        pytest.approx(0.002)
    # a slice clips the spans
    assert sp.idle_inside(t, ["rgl.decode"], 70 * MS, hi) == \
        pytest.approx(0.020)


def test_gaps_named_by_the_innermost_span():
    t = _hand_trace()
    lo, hi = tr.window(t)
    gaps = sp.idle_gaps(t, lo, hi)
    # 60-90: rgl.decode (35 ms) covers all of it inside engine_step (40);
    # 30-50: rgl.admit; 0-10: no span covers more than the step's
    assert [g[0] for g in gaps] == ["rgl.decode", "rgl.admit", "rag_step"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])
    # the harness's own naming is unchanged
    assert [g[0] for g in tr.idle_gaps(t, lo, hi)] == \
        ["engine_step", "rag_step", "rag_step"]


def test_span_readers_on_the_hand_trace():
    run = _run(_hand_trace())
    assert sp.linearize_ms_per_q(run) == pytest.approx(4.0)
    assert sp.admit_idle_ms_per_wave(run) == pytest.approx(20.0)
    assert sp.decode_idle_ms_per_step(run) == pytest.approx(18.5)


def test_span_readers_read_nothing_without_spans():
    # a trace from a harness that keeps no program spans, or a program
    # that opens none
    for t in (_hand_trace(with_spans=False), None):
        run = types.SimpleNamespace(
            trace=t, trace_window=tr.window(t) if t else None)
        assert sp.linearize_ms_per_q(run) is None
        assert sp.admit_idle_ms_per_wave(run) is None
        assert sp.decode_idle_ms_per_step(run) is None
    t = _hand_trace()
    t.spans = [s for s in t.spans if s[0] == "rgl.step"]
    run = _run(t)
    assert sp.linearize_ms_per_q(run) is None
    assert sp.admit_idle_ms_per_wave(run) is None
    assert sp.decode_idle_ms_per_step(run) is None


def _rec(ok, submitted=None, launched=None, stamped=True):
    req = types.SimpleNamespace()
    if stamped:
        req.submitted_at, req.launched_at = submitted, launched
    return types.SimpleNamespace(ok=ok, req=req)


@pytest.mark.parametrize("recs,loop,want", [
    ([_rec(True, 1.0, 1.010), _rec(True, 2.0, 2.030),
      _rec(True, 3.0, 3.020)], "open", 20.0),
    # an unserved request counts as infinite
    ([_rec(True, 1.0, 1.010), _rec(False), _rec(False)], "open", math.inf),
    # a program without the stamps reads nothing
    ([_rec(True, stamped=False)], "open", None),
    ([_rec(True, 1.0, 1.010)], "closed", None),
    ([], "open", None),
], ids=["median", "unserved", "no-stamps", "closed", "empty"])
def test_queue_wait_reader(recs, loop, want):
    read = H.metric_reader("queue_wait_ms.open")
    run = types.SimpleNamespace(loop=loop, traced_recs=lambda: recs)
    got = read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def _fixture(name):
    d = json.loads((FIXTURES / name).read_text())
    t = tr.Trace.from_json(d)
    if "spans" in d:
        t.spans = [(n, st, dur, stats) for n, st, dur, stats in d["spans"]]
    return t


def test_recorded_tpu_slice_with_spans():
    t = _fixture("tpu_trace_slice_spans.json")
    run = _run(t)
    lo, hi = run.trace_window
    for read in (sp.linearize_ms_per_q, sp.admit_idle_ms_per_wave,
                 sp.decode_idle_ms_per_step):
        v = read(run)
        assert v is not None and math.isfinite(v) and v >= 0, read.__name__
    idle = (hi - lo) / 1e9 - tr.busy_s(t, lo, hi)
    names = {s[0] for s in t.spans}
    assert sp.idle_inside(t, names, lo, hi) <= idle + 1e-9
    gaps = sp.idle_gaps(t, lo, hi)
    assert gaps and gaps[0][0].startswith(sp.PREFIX)
    assert sum(g for _, g in gaps) <= idle + 1e-9


def test_existing_readers_unchanged_on_the_older_slice():
    t = _fixture("tpu_trace_slice.json")
    lo, hi = tr.window(t)
    assert tr.busy_s(t, lo, hi) == pytest.approx(0.055607599, rel=1e-12)
    assert tr.top_modules(t, lo, hi) == \
        [["jit_serve_step", pytest.approx(0.055607599, rel=1e-12)]]
    assert tr.idle_gaps(t, lo, hi) == [
        ["engine_step", pytest.approx(0.002338928, rel=1e-12)],
        ["engine_step", pytest.approx(0.002053473, rel=1e-12)]]
    run = types.SimpleNamespace(trace=t, trace_window=(lo, hi), counters={})
    assert H.metric_reader("decode_step_ms.closed")(run) == \
        pytest.approx(0.055607599e3 / 3, rel=1e-12)
    assert H.metric_reader("idle_share.closed")(run) == \
        pytest.approx(100 * (1 - 0.055607599 / 0.06), rel=1e-12)
    # the slice holds no retrieval program
    assert H.metric_reader("retrieval_ms_per_q.closed")(run) is None


def test_read_keeps_the_program_spans_of_a_capture(tmp_path):
    import jax

    from repro import tracing

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("decode", live=3):
            with tracing.span("decode.wait"):
                pass
        with jax.profiler.TraceAnnotation("bench:rag_step"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = {name: stats for name, _, _, stats in sp.read(tmp_path)}
    assert got["rgl.decode"] == {"live": 3}
    assert got["rgl.decode.wait"] == {}
    assert "bench:rag_step" not in got
    assert list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))  # kept
