"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV blocks:
  * retrieval_scaling   — paper Fig. 2/4 (naive vs RGL, per query count)
  * modality_completion — paper Table 1 (R@20 / N@20 per method)
  * abstract_generation — paper Table 2 (ROUGE-1/2/L per context)
  * kernels             — microbench of the Pallas-kernel reference paths
  * serving             — fused RAG serving (also writes BENCH_rag_serving.json)
  * async_serving       — sync vs prefetched admission at several retrieval
                          costs (also writes BENCH_async_serving.json)
  * sharding            — sharded index + tiled IVF scan (also writes
                          BENCH_index_sharding.json)
  * scaling             — dense vs workset-compacted subgraph construction
                          over a corpus-size sweep (also writes
                          BENCH_retrieval_scaling.json)
  * spec_decode         — self-speculative vs one-token decode across draft
                          windows and prompt repetitiveness (also writes
                          BENCH_spec_decode.json)
  * paged_kv            — paged-arena indirection overhead + wave vs
                          continuous admission on a skewed request mix
                          (also writes BENCH_paged_kv.json)
  * fault_tolerance     — goodput vs injected retrieval-fault rate, with
                          and without retries + the degradation ladder
                          (also writes BENCH_fault_tolerance.json)
  * multi_replica       — goodput/latency vs replica count behind the
                          health-aware router, plus crash-mid-run failover
                          vs the naive (stranding) router (also writes
                          BENCH_multi_replica.json)
  * prefix_sharing      — block-level prefix sharing on a repeated-query
                          workload: admission latency + prefill rows +
                          peak pool residency, share on vs off (also
                          writes BENCH_prefix_sharing.json)
  * online_mutation     — serving goodput under a live write mix (streaming
                          graph/index mutations vs the frozen store), plus
                          a staleness probe and a compaction-parity check
                          (also writes BENCH_online_mutation.json)
Roofline (§Roofline/§Perf) is separate: ``python -m benchmarks.roofline``
reads the dry-run artifacts.

``--fast`` shrinks sizes for local iteration.  ``--smoke`` shrinks further
(tiny sizes, one repeat, single sweep points) so CI can run EVERY section on
every PR and upload the emitted ``BENCH_*.json`` artifacts — benchmarks that
only a human ever runs rot silently.  Reduced tiers write ``*.smoke.json``
so they never clobber the committed full-run artifacts.
"""
from __future__ import annotations

import argparse


def main() -> None:
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=[
        "retrieval", "completion", "abstract", "kernels", "serving",
        "async_serving", "sharding", "scaling", "spec_decode", "paged_kv",
        "fault_tolerance", "multi_replica", "prefix_sharing",
        "online_mutation",
    ])
    ap.add_argument("--fast", action="store_true",
                    help="smaller graphs / fewer queries")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke tier: tiny sizes, one repeat — checks "
                         "every section still runs and emits its BENCH json")
    args = ap.parse_args()
    smoke = args.smoke
    fast = args.fast or smoke

    def bench_path(name: str) -> str:
        """Smoke/fast tiers must never clobber the committed full-run
        BENCH_*.json artifacts: reduced-size runs write *.smoke.json
        (still matched by CI's BENCH_*.json artifact glob)."""
        return f"BENCH_{name}.smoke.json" if fast else f"BENCH_{name}.json"

    from benchmarks import (
        abstract_generation, async_serving, fault_tolerance, index_sharding,
        kernels, modality_completion, multi_replica, online_mutation,
        paged_kv, prefix_sharing, rag_serving, retrieval_scaling,
        spec_decode,
    )

    print("name,us_per_call,derived")
    if args.only in (None, "retrieval"):
        kw = {} if not fast else (
            dict(n_nodes=1000, query_counts=(10,)) if smoke else
            dict(n_nodes=4000, query_counts=(10, 100)))
        for r in retrieval_scaling.run(**kw):
            print(f"retrieval/{r['name']}@q={r['queries']},"
                  f"{r['seconds'] * 1e6:.0f},speedup={r['speedup']:.1f}x")
    if args.only in (None, "completion"):
        kw = {} if not fast else (
            dict(n_users=150, n_items=80, n_inter=1500) if smoke else
            dict(n_users=300, n_items=150, n_inter=3000))
        for r in modality_completion.run(**kw):
            print(f"completion/{r['name']},0,"
                  f"R@20={r['r@20']:.4f};N@20={r['n@20']:.4f};mse={r['mse']:.3f}")
    if args.only in (None, "abstract"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_queries=8) if smoke else
            dict(n_nodes=1000, n_queries=16))
        for r in abstract_generation.run(**kw):
            print(f"abstract/{r['name']},0,"
                  f"R1={r['rouge1']:.4f};R2={r['rouge2']:.4f};RL={r['rougeL']:.4f}")
    if args.only in (None, "kernels"):
        for r in kernels.run():
            print(f"kernels/{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.only in (None, "serving"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=6, max_new=6) if smoke else
            dict(n_nodes=1000, n_requests=8, max_new=8))
        r = rag_serving.run(**kw)
        rag_serving.write_json(r, bench_path("rag_serving"))
        print(f"serving/fused_vs_seq,{r['fused_s'] * 1e6:.0f},"
              f"ratio={r['throughput_ratio']:.1f}x;"
              f"replay={r['replay_speedup']:.2f}x")
    if args.only in (None, "async_serving"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=8, max_new=6, repeats=1,
                 cost_ratios=(1.0,)) if smoke else
            dict(n_nodes=1000, n_requests=12, max_new=8))
        rep = async_serving.run(**kw)
        async_serving.write_json(rep, bench_path("async_serving"))
        for r in rep["results"]:
            print(f"async_serving/cost={r['cost_ratio']:.1f}x,"
                  f"{r['prefetch_s'] * 1e6:.0f},"
                  f"speedup={r['speedup']:.2f}x;"
                  f"hidden={r['hidden_frac']:.2f}")
    if args.only in (None, "sharding"):
        sizes = (50_000, 200_000) if not fast else (
            (10_000,) if smoke else (20_000, 50_000))
        rep = index_sharding.run(corpus_sizes=sizes)
        index_sharding.write_json(rep, bench_path("index_sharding"))
        for r in rep["results"]:
            print(f"sharding/n={r['n']},{r['brute_sharded_s'] * 1e6:.0f},"
                  f"brute_sharded={r['brute_sharded_speedup']:.2f}x;"
                  f"ivf_tiled={r['ivf_tiled_speedup']:.2f}x")
    if args.only in (None, "scaling"):
        kw = {} if not fast else (
            dict(corpus_sizes=(20_000,), repeats=1, n_queries=8) if smoke
            else dict(corpus_sizes=(20_000, 50_000), repeats=1))
        rep = retrieval_scaling.run_corpus_sweep(**kw)
        retrieval_scaling.write_json(rep, bench_path("retrieval_scaling"))
        for r in rep["results"]:
            spd = "-" if r["speedup"] is None else f"{r['speedup']:.2f}x"
            print(f"scaling/{r['strategy']}@n={r['n']},"
                  f"{r['compact_s'] * 1e6:.0f},dense_vs_compact={spd};"
                  f"overflow={r['compact_overflow_frac']:.2f}")
    if args.only in (None, "spec_decode"):
        kw = {} if not fast else (
            dict(n_requests=6, max_new=24, cache_len=96, repeats=1,
                 windows=(4,), regimes=("repetitive",)) if smoke else
            dict(n_requests=8, max_new=64, cache_len=160, repeats=2,
                 windows=(2, 4)))
        rep = spec_decode.run(**kw)
        spec_decode.write_json(rep, bench_path("spec_decode"))
        for r in rep["results"]:
            print(f"spec_decode/{r['regime']}@W={r['draft_window']},"
                  f"{r['spec_s'] * 1e6:.0f},"
                  f"speedup={r['speedup']:.2f}x;"
                  f"tok_per_step={r['tokens_per_step']:.2f}")
    if args.only in (None, "paged_kv"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=8, short_new=4, long_new=16,
                 repeats=1) if smoke else
            dict(n_nodes=1000, n_requests=12, short_new=6, long_new=24))
        rep = paged_kv.run(**kw)
        paged_kv.write_json(rep, bench_path("paged_kv"))
        ind, skew = rep["indirection"], rep["skewed_admission"]
        print(f"paged_kv/indirection,{ind['paged_s'] * 1e6:.0f},"
              f"overhead={ind['paged_overhead'] * 100:+.1f}%;"
              f"residency={ind['kv_residency_frac']:.2f}")
        print(f"paged_kv/skewed_admission,{skew['continuous_s'] * 1e6:.0f},"
              f"continuous_vs_wave={skew['speedup']:.2f}x")
    if args.only in (None, "fault_tolerance"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=8, max_new=6,
                 fault_rates=(0.0, 0.2), timeout_s=0.1) if smoke else
            dict(n_nodes=1000, n_requests=12, max_new=8,
                 fault_rates=(0.0, 0.2, 0.4)))
        rep = fault_tolerance.run(**kw)
        fault_tolerance.write_json(rep, bench_path("fault_tolerance"))
        for row in rep["results"]:
            res, nai = row["resilient"], row["naive"]
            print(f"fault_tolerance/rate={row['fault_rate']:.0%},"
                  f"{res['wall_s'] * 1e6:.0f},"
                  f"goodput={res['goodput_tok_s']:.1f}tok_s;"
                  f"ok={res['completed']};failed={res['failed']};"
                  f"degraded={res['degraded_served']};"
                  f"naive_ok={nai['completed']}")
    if args.only in (None, "multi_replica"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=8, max_new=6, slots=3,
                 replica_counts=(1, 2), crash_step=2) if smoke else
            dict(n_nodes=1000, n_requests=12, max_new=8,
                 replica_counts=(1, 2, 3)))
        rep = multi_replica.run(**kw)
        multi_replica.write_json(rep, bench_path("multi_replica"))
        for row in rep["scaling"]:
            print(f"multi_replica/replicas={row['replicas']},"
                  f"{row['wall_s'] * 1e6:.0f},"
                  f"goodput={row['goodput_tok_s']:.1f}tok_s;"
                  f"p99={row['p99_s'] * 1e3:.0f}ms")
        c = rep["crash"]
        fo, na = c["failover_3_with_crash"], c["naive_3_with_crash"]
        print(f"multi_replica/crash,{fo['wall_s'] * 1e6:.0f},"
              f"ratio_vs_2healthy={c['goodput_ratio_vs_2healthy']:.2f}x;"
              f"redispatched={fo['redispatched']};"
              f"naive_stranded={na['stranded']}")
    if args.only in (None, "prefix_sharing"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=8, n_unique=2, slots=3,
                 max_new=6, repeats=1) if smoke else
            dict(n_nodes=1000, n_requests=16, n_unique=2, slots=4,
                 repeats=2))
        rep = prefix_sharing.run(**kw)
        prefix_sharing.write_json(rep, bench_path("prefix_sharing"))
        adm, res = rep["admission"], rep["residency"]
        print(f"prefix_sharing/admission,{adm['admit_on_s'] * 1e6:.0f},"
              f"speedup={adm['admit_speedup']:.2f}x;"
              f"shared_frac={adm['shared_admit_frac']:.2f};"
              f"prefill_rows={adm['prefill_rows_off']}->"
              f"{adm['prefill_rows_on']}")
        print(f"prefix_sharing/residency,{res['high_water_on_blocks']:.0f},"
              f"frac_vs_unshared={res['residency_frac_vs_unshared']:.2f};"
              f"pinned={res['pinned_blocks_final']}")
    if args.only in (None, "online_mutation"):
        kw = {} if not fast else (
            dict(n_nodes=500, n_requests=8, slots=3, max_new=6,
                 n_probes=2) if smoke else
            dict(n_nodes=1000, n_requests=12, max_new=8, n_probes=3))
        rep = online_mutation.run(**kw)
        online_mutation.write_json(rep, bench_path("online_mutation"))
        m = rep["mutating"]
        print(f"online_mutation/write_mix={rep['write_mix']:.0%},"
              f"{m['wall_s'] * 1e6:.0f},"
              f"goodput_ratio={rep['goodput_ratio']:.2f}x;"
              f"epoch={m['mutation_epoch']};"
              f"invalidated={m['mutation_invalidated']};"
              f"fresh={rep['staleness']['fresh_frac']:.2f};"
              f"parity={'ok' if rep['parity']['ok'] else 'BROKEN'}")


if __name__ == "__main__":
    main()
