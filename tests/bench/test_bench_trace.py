"""The trace reduction: a hand-built trace with known answers, and a small
slice of a trace recorded on a TPU v5 lite (committed as a fixture)."""
import json

import pytest

from bench import trace as tr
from benchutil import FIXTURES

MS = 1_000_000  # ns


def _hand_trace():
    # window 0..100 ms; device busy 10-30 (two overlapping programs),
    # 50-60 and 90-110 (clipped to 100); idle 0-10, 30-50, 60-90
    modules = [("jit_serve_step", 10 * MS, 15 * MS, "/device:TPU:0"),
               ("jit_topk_similarity", 20 * MS, 10 * MS, "/device:TPU:0"),
               ("jit_serve_step", 50 * MS, 10 * MS, "/device:TPU:0"),
               ("jit__prefill_batch", 90 * MS, 20 * MS, "/device:TPU:0")]
    ops = [("topk_sim_blocks", "jit_topk_similarity", 21 * MS, 4 * MS,
            "/device:TPU:0"),
           ("fusion.3", "jit_serve_step", 11 * MS, 2 * MS, "/device:TPU:0")]
    host = [("bench:window", 0, 100 * MS),
            ("bench:rag_step", 0, 100 * MS),
            ("bench:linearize", 30 * MS, 20 * MS),
            ("bench:engine_step", 60 * MS, 40 * MS)]
    return tr.Trace(modules=modules, ops=ops, host=host, devices=1)


def test_window_busy_and_idle_share():
    t = _hand_trace()
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 100 * MS)
    assert tr.busy_s(t, lo, hi) == pytest.approx(0.040)
    assert tr.union_ns([(0, 5), (3, 8), (10, 12)]) == 10


def test_module_and_op_sums():
    t = _hand_trace()
    lo, hi = tr.window(t)
    assert tr.module_time(t, ["jit_serve_step"], lo, hi) == \
        pytest.approx((0.025, 2))
    # the prefill runs 90-110 ms: only its 10 ms inside the window count
    assert tr.module_time(t, ["jit_topk_similarity", "jit__prefill"],
                          lo, hi) == pytest.approx((0.020, 2))
    assert tr.op_time(t, ["topk_sim"], lo, hi) == pytest.approx((0.004, 1))
    assert tr.module_name("jit_serve_step(17)") == "jit_serve_step"


def test_breakdown_names_gaps_by_host_span():
    t = _hand_trace()
    lo, hi = tr.window(t)
    top = tr.top_modules(t, lo, hi)
    assert top[0] == ["jit_serve_step", pytest.approx(0.025)]
    gaps = tr.idle_gaps(t, lo, hi)
    assert [g[0] for g in gaps] == ["engine_step", "linearize", "rag_step"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])


def test_recorded_tpu_slice():
    t = tr.Trace.from_json(json.loads(
        (FIXTURES / "tpu_trace_slice.json").read_text()))
    lo, hi = tr.window(t)
    window = (hi - lo) / 1e9
    busy = tr.busy_s(t, lo, hi)
    assert 0 < busy <= window
    top = tr.top_modules(t, lo, hi)
    assert 0 < len(top) <= 10
    assert sum(v for _, v in top) >= busy * 0.999
    gaps = tr.idle_gaps(t, lo, hi)
    assert len(gaps) <= 10
    assert sum(g for _, g in gaps) <= window - busy + 1e-9
    s, n = tr.module_time(t, [top[0][0]], lo, hi)
    assert n > 0 and s == pytest.approx(top[0][1])
