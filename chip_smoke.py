#!/usr/bin/env python3
"""Bring-up smoke: the fused RAG serving path end to end on one TPU chip.

Drives index -> BFS subgraph -> filter -> linearize -> prefill -> decode
through the normal entry points (``RGLPipeline``, ``RAGServeEngine``, the
``repro.launch.serve`` builders) with starcoder2-3b at its published widths
(random weights from ``--seed``) over a seeded citation graph at the
paper's arxiv scale (169,343 nodes, 128-d features), and checks what comes
out:

* retrieval -- ``BruteIndex`` (the ``topk_sim`` kernel at this N) and
  compact BFS via ``retrieve_many``; the kernel's top-k ids agree with the
  op's jnp arm on the same chip, near-ties excepted;
* serving -- ``RAGServeEngine`` on the contiguous and the paged arena with
  no degraded mode and no retries: every request is served with retrieval;
* numerics -- cached-decode greedy tokens equal the argmax of a full
  forward pass wherever the top-2 logit gap is above a bf16 tolerance.

``--four-chips`` runs only the multi-chip phase instead: ``ShardedIndex``
(brute and IVF inners) over 4 chips at the OGBN-products shape
(2,449,029 x 100) against ``BruteIndex`` on one chip.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips

Times printed here are smoke timings (wall seconds of one cold and one
warm pass, compilation included in the cold one), not benchmark figures.
Any failed check raises; the last line of stdout is the JSON verdict
``{"ok": true, "device": {...}}``, printed only when every phase passed.
With no TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

ARCH = "starcoder2-3b"
ARXIV_NODES = 169_343  # configs/rgl_paper.py arxiv_nodes
PRODUCTS_ROWS, PRODUCTS_DIM = 2_449_029, 100  # configs/common.py ogb_products
# scores of two different ids count as tied below this gap: unit-norm
# dot products at the chip's default (single bf16 pass) matmul precision
# carry errors of order 2^-8; this is a few times that
TIE_TOL = 1e-2
# top-2 logit gap above which cached decode and the full forward must pick
# the same token: both run in bf16 through 30 layers along different
# attention paths, so smaller gaps may flip
GAP_TOL = 0.25


def _say(*parts) -> None:
    print(*parts, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _timed(fn):
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def _retrieved(pipe, q):
    """(RetrievalResult, its subgraph) -- the subgraph is what
    ``block_until_ready`` can wait on."""
    res = pipe.retrieve_many(q)
    return res, res.sub


def exact_scores(qn, table, ids):
    """Float64 host scores of the listed (Q, k) ids: only those rows of the
    device table are gathered."""
    import numpy as np

    ids = np.asarray(ids)
    rows = np.asarray(table[ids.ravel()], np.float64)
    return np.einsum("qd,qkd->qk", np.asarray(qn, np.float64),
                     rows.reshape(*ids.shape, -1))


def topk_agreement(qn, table, ids_a, ids_b, tol: float) -> dict:
    """Compare two (Q, k) id lists for the same queries: at every rank the
    ids agree, or their exact scores are within ``tol`` (a near-tie)."""
    import numpy as np

    ids_a, ids_b = np.asarray(ids_a), np.asarray(ids_b)
    same = ids_a == ids_b
    gap = np.where(same, 0.0, np.abs(exact_scores(qn, table, ids_a)
                                     - exact_scores(qn, table, ids_b)))
    return {"exact_share": float(same.mean()), "max_swap_gap": float(gap.max()),
            "ok": bool((gap <= tol).all())}


def exact_kth(qn, table, k: int, chunk: int = 262_144):
    """(Q,) exact float64 k-th best score over the whole table, streamed
    through the host in row chunks."""
    import numpy as np

    qn = np.asarray(qn, np.float64)
    best = np.full((qn.shape[0], 0), -np.inf)
    for lo in range(0, table.shape[0], chunk):
        part = qn @ np.asarray(table[lo:lo + chunk], np.float64).T
        best = -np.sort(-np.concatenate([best, part], axis=1), axis=1)[:, :k]
    return best[:, k - 1]


# --------------------------------------------------------------------------
# phases (each takes its sizes, so a CPU test runs them tiny)
# --------------------------------------------------------------------------
def build_stack(n_nodes: int, *, max_len: int, seed: int):
    """Seeded corpus + compact-BFS pipeline over a brute index, built with
    the launcher's own helpers.  Returns (graph, embeddings, pipeline)."""
    from repro import configs as C
    from repro.core import PipelineConfig
    from repro.launch import serve

    paper = C.RGL_PAPER
    g, ell, emb = serve.build_corpus(n_nodes, avg_deg=8, seed=seed)
    pcfg = PipelineConfig(
        strategy="bfs", k_seeds=paper.k_seeds, max_hops=paper.max_hops,
        max_nodes=paper.max_nodes, filter_budget=paper.filter_budget,
        index_kind="brute",
        # "auto" would re-run an overflowing batch on the dense backend,
        # whose (Q, N, K) gathers do not fit at this scale
        retrieval_mode="compact",
    )
    tok = serve.graph_tokenizer(g, max_len=max_len, node_budget=48)
    _say(f"corpus: {g.num_nodes} nodes, {int(g.indices.size)} arcs, "
         f"ELL width {ell.max_deg}, "
         f"adjacency {(ell.nbr.nbytes + ell.nbr_mask.nbytes) / 2**30:.3f} "
         f"GiB, embeddings {emb.shape}")
    return g, emb, serve.build_rag_pipeline(g, ell, emb, pcfg, tok)


def phase_retrieval(pipe, emb, *, n_queries: int, seed: int,
                    tol: float = TIE_TOL) -> dict:
    """retrieve_many over seeded queries; the index's top-k agrees with
    the topk_sim op's jnp arm and with the exact host top-k."""
    import numpy as np

    from repro.core.indexing import l2_normalize
    from repro.kernels.topk_sim import ops as topk_ops

    n = pipe.n_valid_nodes
    rng = np.random.default_rng(seed)
    picks = rng.choice(n, size=n_queries, replace=False)
    q = np.asarray(emb)[picks] + 0.1 * rng.standard_normal(
        (n_queries, emb.shape[1])).astype(np.float32)
    (res, _), t_cold = _timed(lambda: _retrieved(pipe, q))
    _, t_warm = _timed(lambda: _retrieved(pipe, q))
    nodes, mask = np.asarray(res.nodes), np.asarray(res.mask)
    overflow = np.asarray(res.overflow)
    _say(f"retrieval: {n_queries} queries, compact BFS, overflow share "
         f"{overflow.mean():.4f}, nodes kept per query "
         f"{mask.sum(1).min()}..{mask.sum(1).max()}; smoke timings "
         f"cold {t_cold:.3f}s warm {t_warm:.3f}s")
    _check(bool(mask.any(1).all()), "every query keeps retrieved nodes")
    _check(bool((nodes[mask] < n).all()), "retrieved ids are in range")
    _check(all(len(set(r[m])) == int(m.sum()) for r, m in zip(nodes, mask)),
           "retrieved ids are distinct per query")

    k = pipe.config.k_seeds
    table = pipe.index.emb
    qn = l2_normalize(np.asarray(q, np.float32))
    arms = {}
    for arm in (True, False):  # the first call of each arm compiles
        for _ in range(2):
            arms[arm] = _timed(lambda: topk_ops.topk_similarity(
                qn, table, k, use_kernel=arm))
    (_, ids_kernel), t_kernel = arms[True]
    (_, ids_jnp), t_jnp = arms[False]
    ids_kernel = np.asarray(ids_kernel)
    _check(np.array_equal(np.asarray(res.seeds), ids_kernel),
           "the pipeline's seeds are the index op's top-k")
    _check(all(len(set(r)) == k for r in ids_kernel),
           "top-k ids are distinct per query")
    agree = topk_agreement(qn, table, ids_kernel, ids_jnp, tol)
    short = float((exact_kth(qn, table, k)[:, None]
                   - exact_scores(qn, table, ids_kernel)).max())
    _say(f"topk_sim kernel arm vs jnp arm: "
         f"exact id share {agree['exact_share']:.4f}, largest score gap "
         f"between swapped ids {agree['max_swap_gap']:.2e} (tie tolerance "
         f"{tol}); deepest shortfall below the exact k-th score "
         f"{short:.2e}; smoke timings (warm) {t_kernel:.4f}s / "
         f"{t_jnp:.4f}s")
    _check(agree["ok"], "kernel and jnp top-k agree up to near-ties")
    _check(short <= tol, "kernel top-k is the exact top-k up to near-ties")
    return {"overflow_share": float(overflow.mean()),
            "exact_id_share": agree["exact_share"]}


def serve_pass(pipe, g, emb, params, cfg, *, paged: bool, n_requests: int,
               max_new: int, slots: int, seed: int) -> list:
    """One engine, ``n_requests`` seeded requests, served to completion;
    every request must come back with retrieval and the full token budget."""
    import numpy as np

    from repro.launch import serve
    from repro.serving import RAGRequest, RAGServeEngine, ServingConfig

    conf = ServingConfig(
        slots=slots, cache_len=serve.arena_len(cfg, pipe.tokenizer, max_new),
        paged_kv=paged, degraded_mode=False, max_retries=0,
    )
    eng = RAGServeEngine(pipe, params, cfg, config=conf)
    rng = np.random.default_rng(seed)
    emb_np = np.asarray(emb)
    for u, qi in enumerate(rng.choice(pipe.n_valid_nodes, size=n_requests,
                                      replace=False)):
        eng.submit(RAGRequest(
            uid=u, query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=max_new,
        ))
    done = eng.run_to_completion()
    s = eng.stats()
    for r in done:
        if r.failed or r.shed:
            _say(f"  request {r.uid}: {r.error}")
    counters = {key: s[key] for key in ("failed", "degraded", "stale_served",
                                        "shed", "truncations", "retries")}
    _check(len(done) == n_requests, f"{len(done)}/{n_requests} returned")
    _check(not any(counters.values()), f"no fault counters: {counters}")
    for r in done:
        _check(r.done and not (r.failed or r.degraded or r.stale or r.shed
                               or r.truncated), f"request {r.uid} served")
        _check(r.retrieved_nodes is not None and len(r.retrieved_nodes) > 0,
               f"request {r.uid} has retrieved nodes")
        _check(len(r.out_tokens) == max_new, f"request {r.uid} token count")
    return sorted(done, key=lambda r: r.uid)


def phase_serving(pipe, g, emb, params, cfg, *, paged: bool, n_requests: int,
                  max_new: int, slots: int, seed: int) -> list:
    """A cold and a warm serving pass on one arena kind."""
    runs = []
    for i, label in enumerate(("cold", "warm")):
        t = time.perf_counter()
        done = serve_pass(pipe, g, emb, params, cfg, paged=paged,
                          n_requests=n_requests, max_new=max_new, slots=slots,
                          seed=seed + i)
        runs.append((label, time.perf_counter() - t, done))
    toks = sum(len(r.out_tokens) for _, _, d in runs for r in d)
    prompt = [len(r.prompt_ids) for _, _, d in runs for r in d]
    _say(f"serving ({'paged' if paged else 'contiguous'} arena): "
         f"{2 * n_requests} requests, {toks} tokens served, prompts "
         f"{min(prompt)}..{max(prompt)} tokens, retrieved nodes per request "
         f"{min(len(r.retrieved_nodes) for _, _, d in runs for r in d)}.."
         f"{max(len(r.retrieved_nodes) for _, _, d in runs for r in d)}, "
         f"0 failed/degraded/stale/shed/truncated/retries; smoke timings "
         + ", ".join(f"{label} {dt:.2f}s" for label, dt, _ in runs))
    return runs[0][2]


def phase_numerics(params, cfg, done: list, *, n_check: int,
                   gap_tol: float = GAP_TOL) -> dict:
    """Rescore prompt + output with a full forward (``tm.lm_logits``): the
    cached-decode greedy tokens must equal its argmax wherever the top-2
    gap is above ``gap_tol``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import model as tm

    reqs = done[:n_check]
    seqs = [np.concatenate([r.prompt_ids, r.out_tokens[:-1]]) for r in reqs]
    # chunked attention wants whole chunks; causal, so tail padding is inert
    unit = math.lcm(cfg.q_chunk, cfg.kv_chunk)
    width = -(-max(len(s) for s in seqs) // unit) * unit
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    fwd = jax.jit(tm.lm_logits, static_argnames=("cfg",))
    logits, t_cold = _timed(lambda: fwd(params, jnp.asarray(toks), cfg))
    logits = np.asarray(logits)
    agree = checked = checked_agree = total = 0
    for i, r in enumerate(reqs):
        lp = len(r.prompt_ids)
        rows = logits[i, lp - 1:lp - 1 + len(r.out_tokens)]
        top2 = np.sort(rows, axis=1)[:, -2:]
        gap = top2[:, 1] - top2[:, 0]
        hit = rows.argmax(1) == np.asarray(r.out_tokens)
        agree += int(hit.sum())
        total += len(hit)
        wide = gap > gap_tol
        checked += int(wide.sum())
        checked_agree += int((hit & wide).sum())
    _say(f"numerics: cached decode vs full forward argmax, {n_check} "
         f"requests: agreement {agree}/{total} = {agree / total:.4f} overall,"
         f" {checked_agree}/{checked} where the top-2 gap > {gap_tol}; "
         f"smoke timing (forward, cold) {t_cold:.2f}s")
    _check(checked_agree == checked,
           "decode agrees with the full forward above the gap tolerance")
    return {"agreement": agree / total, "checked": checked}


def phase_four_chips(devices, *, rows: int, dim: int, n_queries: int, k: int,
                     n_clusters: int, seed: int, tol: float = TIE_TOL) -> None:
    """ShardedIndex over ``devices`` (one shard each, brute and exhaustive
    IVF inners) against BruteIndex on ``devices[0]``."""
    import jax
    import numpy as np

    from repro.core import BruteIndex, ShardedIndex
    from repro.core.indexing import l2_normalize

    with jax.default_device(devices[0]):
        key, kq = jax.random.split(jax.random.PRNGKey(seed))
        emb = jax.random.normal(key, (rows, dim), jax.numpy.float32)
        picks = jax.random.choice(kq, rows, (n_queries,), replace=False)
        q = emb[picks] + 0.1 * jax.random.normal(kq, (n_queries, dim))
        brute, t_build = _timed(lambda: BruteIndex.build(emb).emb)
        brute = BruteIndex(emb=brute)
        (_, ids_one), t_one = _timed(lambda: brute.search(q, k))
    qn = np.asarray(l2_normalize(q))
    _say(f"four-chip: table {rows}x{dim}, {n_queries} queries, k={k}; "
         f"one-chip brute on {devices[0]}: smoke timings build "
         f"{t_build:.2f}s search {t_one:.3f}s")
    for inner in ("brute", "ivf"):
        t = time.perf_counter()
        sharded = ShardedIndex.build(
            emb, n_shards=len(devices), inner=inner, devices=devices,
            n_clusters=n_clusters, nprobe=n_clusters, seed=seed)
        jax.block_until_ready(sharded.emb_shards)
        t_b = time.perf_counter() - t
        held = {}
        for arr in (sharded.emb_shards, sharded.centroids, sharded.lists,
                    sharded.list_mask):
            for sh in getattr(arr, "addressable_shards", ()):
                held[sh.device] = held.get(sh.device, 0) + sh.data.nbytes
        _say(f"  sharded {inner}: bytes per device "
             + ", ".join(f"{d}={held.get(d, 0)}" for d in devices))
        _check(set(held) == set(devices) and min(held.values()) > 0,
               f"sharded {inner} state on all {len(devices)} devices")
        (_, ids), t_cold = _timed(lambda: sharded.search(q, k))
        (_, ids), t_warm = _timed(lambda: sharded.search(q, k))
        agree = topk_agreement(qn, brute.emb, ids, ids_one, tol)
        _say(f"  sharded {inner} vs one-chip brute: exact id share "
             f"{agree['exact_share']:.4f}, largest swapped-score gap "
             f"{agree['max_swap_gap']:.2e} (tie tolerance {tol}); smoke "
             f"timings build {t_b:.2f}s search cold {t_cold:.3f}s warm "
             f"{t_warm:.3f}s")
        _check(agree["ok"], f"sharded {inner} ids agree up to near-ties")


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def _peak(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ShardedIndex phase over 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = setup_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this smoke has no CPU path")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        sys.exit(f"chip_smoke: --four-chips needs 4 TPU devices, JAX sees "
                 f"{len(devices)}")
    dev = devices[0]
    _say(f"device_kind {dev.device_kind}, {len(devices)} device(s); jax "
         f"{jax.__version__}, jaxlib {metadata.version('jaxlib')}, libtpu "
         f"{metadata.version('libtpu')}; compile cache {cache_dir}")
    _say("all times below are smoke timings, not benchmark figures")

    if args.four_chips:
        phase_four_chips(devices[:4], rows=PRODUCTS_ROWS, dim=PRODUCTS_DIM,
                         n_queries=32, k=10, n_clusters=64, seed=args.seed)
    else:
        from repro.launch import serve

        t = time.perf_counter()
        g, emb, pipe = build_stack(ARXIV_NODES, max_len=512, seed=args.seed)
        _say(f"setup: corpus + index built in {time.perf_counter() - t:.1f}s")
        phase_retrieval(pipe, emb, n_queries=32, seed=args.seed)
        t = time.perf_counter()
        cfg, params = serve.build_lm(ARCH, published=True,
                                     vocab_size=pipe.tokenizer.vocab.size,
                                     seed=args.seed)
        jax.block_until_ready(params)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        _say(f"model: {ARCH} published widths (L={cfg.n_layers}, "
             f"d={cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
             f"d_ff={cfg.d_ff}, vocab={cfg.vocab}, window="
             f"{cfg.sliding_window}, {cfg.dtype}), {n_params} parameters, "
             f"init {time.perf_counter() - t:.1f}s")
        done = phase_serving(pipe, g, emb, params, cfg, paged=False,
                             n_requests=8, max_new=16, slots=4,
                             seed=args.seed)
        phase_serving(pipe, g, emb, params, cfg, paged=True, n_requests=8,
                      max_new=16, slots=4, seed=args.seed)
        phase_numerics(params, cfg, done, n_check=2)
    peaks = ", ".join(f"{d.id}:{_peak(d)}" for d in devices[:want])
    _say(f"peak_bytes_in_use per device {peaks}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
