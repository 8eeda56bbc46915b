#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run.  In order: the persistent compile cache is pointed
at the checkout; anything but a TPU (or fewer chips than the cell asks for)
ends the run with a non-zero exit and no result; every ``RGL_*`` variable
is dropped, so scheduling options stay at the engine's defaults; the cell
is built and warmed up on its own traffic (that is ``setup_s``); the
window is served for ``--seconds``; requests due in it are drained; the
served answers are compared with the plain references; and the last line
of stdout is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and with ``--trace 1`` a ``breakdown``), and the
compared numbers beside their limits under ``checks``.

With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the profiler traces the last seconds of the window and the
metrics are the cell's per-layer ones.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for k in [k for k in os.environ if k.startswith("RGL_")]:
        del os.environ[k]

    from bench import harness as H

    cell = H.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU (JAX platform {devices[0].platform!r}); the "
                     f"benchmark has no CPU path")
    if len(devices) < cell.chips:
        return _fail(f"cell {cell.name} needs {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    from bench import flops

    dev = devices[0]
    print(f"bench: {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace}; {dev.device_kind} x{cell.chips}; compile "
          f"cache {cache_dir}", file=sys.stderr, flush=True)
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              devices[:cell.chips], flops.peaks(dev.device_kind))
    print(json.dumps(out), flush=True)
    return 0


def run(cell, seed: int, seconds: float, trace: bool, devices,
        pk: dict, corpus_dir=None) -> dict:
    """One run of ``cell``: the result object of the last line."""
    from bench import harness as H
    from bench import traffic
    from bench import trace as tr

    dev = devices[0]
    counter = H._CompileCounter()
    mix = cell.mix
    b = H.build(cell, seed, corpus_dir)
    stream = traffic.Stream(mix, b.corpus.feat, seed)
    warm = traffic.Stream(mix, b.corpus.feat, seed, warmup=True)
    max_new = stream.max_new()
    eng = H.make_engine(cell, b, max_new)
    rec = H.Recorder(eng, cell, b.texts)
    t = time.perf_counter()
    H.warm_up(rec, warm, mix)
    H.say(f"setup: warm-up {mix['warmup_requests']} requests "
          f"({time.perf_counter() - t:.2f}s)")
    if trace:
        H.wrap_spans(eng)
    setup_s = time.perf_counter() - T_START
    win = H.serve_window(
        rec, stream, mix, seconds,
        trace_slice=min(H.TRACE_SECONDS, seconds) if trace else 0,
        counter=counter)
    window_s = win.t1 - win.t0
    recs = [r for r in rec.done if r.in_window]
    H.say(f"window: {window_s:.3f}s, {len(recs)} requests, {win.tokens} "
          f"tokens; compilations in the window: {win.compiles}")
    if win.late_s:
        late = sorted(win.late_s)
        H.say(f"generator lateness: p50 {late[len(late) // 2] * 1e3:.3f} ms, "
              f"max {late[-1] * 1e3:.3f} ms over {len(late)} arrivals")
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))

    # -- correctness: retrieval on the host, the LM on the device once the
    # engine's state is freed
    lm_s, ret_s = H.sample_checks(rec.done, seed, int(mix["check_tokens"]))
    n_fault, faults = H.check_retrieval(cell, b, ret_s)
    for f in faults[:10]:
        H.say(f"retrieval fault: {f}")
    attempted = len(recs)
    failed = sum(not r.ok for r in recs)
    params = b.params
    del eng, rec.eng
    b.pipe = None
    gc.collect()
    t = time.perf_counter()
    gap, _, n_tok = H.lm_gaps(cell, params, lm_s, max_new)
    H.say(f"reference: {len(lm_s)} requests, {n_tok} served tokens, "
          f"{len(ret_s)} retrievals ({time.perf_counter() - t:.2f}s)")
    limit = cell.config["correct"]["gap_limit"]
    checks = {
        "unserved": {"value": failed, "limit": 0},
        "retrieval_faults": {"value": n_fault, "limit": 0},
        "logit_gap_max": {"value": gap,
                          "limit": limit if limit is not None else 0.0},
    }
    correct = bool(attempted > 0 and len(lm_s) > 0 and len(ret_s) > 0
                   and all(c["value"] <= c["limit"] for c in checks.values()))

    # -- metrics
    view = H.RunView(cell=cell, recs=recs, window_s=window_s,
                     tokens=win.tokens, setup_s=setup_s, peaks=pk, win=win)
    want = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in want:
        v = H.metric_reader(m["name"])(view)
        # an unserved request reads as infinite; the run is then not correct
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and win.trace is not None:
        lo, hi = view.trace_window
        device["busy_s"] = tr.busy_s(win.trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": tr.top_modules(win.trace, lo, hi),
                            "idle_gaps": tr.idle_gaps(win.trace, lo, hi)}
    out["checks"] = checks
    for k, c in checks.items():
        H.say(f"check {k}: {c['value']} (limit {c['limit']})")
    return out


if __name__ == "__main__":
    sys.exit(main())
