#!/usr/bin/env python3
"""Readings that set a cell's ``correct`` limits (not run by the benchmark).

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --seconds 8

One process builds the cell's corpus and pipeline once; for each seed it
makes that seed's weights and engine, serves a short window of the cell's
own traffic at its own load, drains it, and reads on the same sampled
requests:

* the program's widest served-token gap below the float32 reference's
  best, and its retrieval faults (the lower readings);
* the control's widest gap: the reference with every linear layer in
  float8 e4m3 (weights per output channel, inputs per token), scored at
  the same positions by the token it puts first (the upper readings).

Prints one JSON line per seed and a summary line last.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness as H

    cell = H.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    b = None
    rows = []
    for seed in args.seeds:
        if b is None:
            b = H.build(cell, seed)
        else:
            b.params = None
            gc.collect()
            b.params = cell.family.make_params(cell.config["model"], seed)
        row = reading(cell, b, seed, args.seconds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": cell.name,
               "gap_max": max(r["gap"] for r in rows),
               "control_gap_min": min(r["control_gap"] for r in rows),
               "faults_max": max(r["retrieval_faults"] for r in rows),
               "unserved_max": max(r["unserved"] for r in rows)}
    print(json.dumps(summary), flush=True)
    return 0


def reading(cell, b, seed: int, seconds: float) -> dict:
    """One seed's readings: serve a short window of the cell's traffic
    with ``b``'s pipeline and weights, then score the sampled requests
    against the reference and the control."""
    from bench import harness as H
    from bench import traffic

    t = time.perf_counter()
    mix = cell.mix
    stream = traffic.Stream(mix, b.corpus.feat, seed)
    warm = traffic.Stream(mix, b.corpus.feat, seed, warmup=True)
    max_new = stream.max_new()
    eng = H.make_engine(cell, b, max_new)
    rec = H.Recorder(eng, cell, b.texts)
    H.warm_up(rec, warm, mix)
    H.serve_window(rec, stream, mix, seconds)
    recs = [r for r in rec.done if r.in_window]
    lm_s, ret_s = H.sample_checks(rec.done, seed, int(mix["check_tokens"]))
    n_fault, faults = H.check_retrieval(cell, b, ret_s)
    del eng, rec
    gc.collect()
    gap, gap_c, n_tok = H.lm_gaps(cell, b.params, lm_s, max_new, control=True)
    return {"seed": seed, "requests": len(recs),
            "unserved": sum(not r.ok for r in recs),
            "retrieval_faults": n_fault, "faults": faults[:3],
            "gap": gap, "control_gap": gap_c, "tokens_compared": n_tok,
            "seconds": time.perf_counter() - t}


if __name__ == "__main__":
    sys.exit(main())
