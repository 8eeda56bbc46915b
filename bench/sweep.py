#!/usr/bin/env python3
"""Find an open-loop cell's knee once (not run by the benchmark).

    python3 bench/sweep.py --workload <open cell> --rates 2 3 4 --seconds 20

One process builds the cell; for each offered rate it serves the cell's
traffic at that rate for ``--seconds`` and reports the time to first token
(median, 95th percentile) and the backlog: requests submitted but not yet
admitted at the window's end, and whether the admission wait grows from
the window's first half to its second.  The knee is the highest rate whose
backlog does not grow.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness as H
    from bench import traffic

    cell = H.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    b = H.build(cell, args.seed)
    for i, rate in enumerate(args.rates):
        mix = dict(cell.mix, rate_rps=rate)
        stream = traffic.Stream(mix, b.corpus.feat, args.seed + i)
        eng = H.make_engine(cell, b, stream.max_new())
        rec = H.Recorder(eng, cell, b.texts)
        H.warm_up(rec, traffic.Stream(mix, b.corpus.feat, args.seed + i,
                                      warmup=True), mix)
        win = H.serve_window(rec, stream, mix, args.seconds)
        recs = [r for r in rec.done if r.in_window]
        mid = win.t0 + args.seconds / 2
        ttft = [(r.first_at - r.due) * 1e3 if r.ok else float("inf")
                for r in recs]
        first = [t for r, t in zip(recs, ttft) if r.due < mid]
        second = [t for r, t in zip(recs, ttft) if r.due >= mid]
        backlog = sum(r.first_at > win.t1 for r in recs)
        tpot = [(r.done_at - r.first_at) * 1e3 / (r.n_tokens - 1)
                for r in recs if r.ok and r.n_tokens > 1]
        print(json.dumps({
            "rate_rps": rate, "requests": len(recs),
            "served_rps": len(recs) / (win.t1 - win.t0),
            "tokens_per_s": win.tokens / (win.t1 - win.t0),
            "ttft_p50_ms": H.quantile(ttft, 0.5),
            "ttft_p95_ms": H.quantile(ttft, 0.95),
            "tpot_p95_ms": H.quantile(tpot, 0.95),
            "ttft_first_half_ms": H.quantile(first, 0.5),
            "ttft_second_half_ms": H.quantile(second, 0.5),
            "no_first_token_at_close": backlog}), flush=True)
        del eng, rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
