"""Spans and per-request stamps of the serving path, read back from a real
profiler session on the CPU: names, nesting, counts, stamp order, and the
compile counter's attribution to the innermost span."""
import contextlib
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.core import graph_retrieval as gr
from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
    RGLPipeline, Vocab
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    RAGRequest, RAGServeEngine, Request, ServeEngine, ServingConfig,
)

N_NODES = 96
MAX_LEN = 48
CACHE_LEN = 64
SLOTS = 3
N_REQ = 7

# child -> the span it always runs inside
PARENT = {
    "admit": "step",
    "retrieval.launch": "step",
    "retrieval.index": "retrieval.launch",
    "retrieval.subgraph": "retrieval.launch",
    "retrieval.filter": "retrieval.launch",
    "retrieval.wait": "admit",
    "linearize": "admit",
    "prefill": "step",
    "prefill.wait": "prefill",
    "decode": "step",
    "decode.wait": "decode",
}
SERVING_SPANS = set(PARENT) | {"step"}


@pytest.fixture(scope="module")
def stack():
    g = generators.citation_graph(N_NODES, avg_deg=5, seed=3)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=MAX_LEN, node_budget=4)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=2, max_hops=2,
                              max_nodes=12, filter_budget=6),
    )
    cfg = TransformerConfig(
        name="tracing-t", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _requests(g):
    return [RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                       query_text=g.node_text[u], max_new_tokens=2 + u % 3)
            for u in range(N_REQ)]


def _read_spans(log_dir) -> list:
    """(short name, start_ns, end_ns, stats) of every ``rgl.`` host event."""
    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.append((e.name[len(tracing.PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _serve_traced(stack, log_dir, prefetch: bool):
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=SLOTS, cache_len=CACHE_LEN, prefetch=prefetch))
    reqs = _requests(g)
    for r in reqs:
        eng.submit(r)
    steps = 0
    jax.profiler.start_trace(str(log_dir))
    try:
        done = []
        while len(done) < N_REQ:
            done.extend(eng.step())
            steps += 1
    finally:
        jax.profiler.stop_trace()
    assert all(r.done and not r.failed for r in done)
    return eng, reqs, steps, _read_spans(log_dir)


@pytest.mark.parametrize("prefetch", [False, True], ids=["sync", "prefetch"])
def test_spans_names_nesting_and_counts(stack, tmp_path, prefetch):
    eng, reqs, steps, spans = _serve_traced(stack, tmp_path, prefetch)
    names = [s[0] for s in spans]
    assert set(names) - {"gc"} == SERVING_SPANS
    for name, st, en, _ in spans:
        parent = PARENT.get(name)
        if parent is None:
            continue
        assert any(p == parent and pst <= st and en <= pen
                   for p, pst, pen, _ in spans), (name, parent)
    assert names.count("step") == steps
    assert sorted(s[3]["uid"] for s in spans if s[0] == "linearize") == \
        list(range(N_REQ))  # one per admitted request, named by its uid
    assert names.count("decode") == eng.engine.decode_steps
    assert names.count("decode.wait") == eng.engine.decode_steps
    assert names.count("prefill") == eng.engine.prefill_batches
    assert names.count("retrieval.launch") == eng.retrieval_batches
    # args come back as event stats, the name left clean
    first = next(s for s in spans if s[0] == "step")
    assert first[3] == {"pending": N_REQ, "live": 0, "inflight": 0}
    pre = [s for s in spans if s[0] == "prefill"]
    assert sum(s[3]["rows"] for s in pre) == N_REQ
    assert all(s[3]["bucket"] in (8, 16, 32, 64) for s in pre)
    dec = [s for s in spans if s[0] == "decode"]
    assert all(1 <= s[3]["live"] <= SLOTS for s in dec)


@pytest.mark.parametrize("prefetch", [False, True], ids=["sync", "prefetch"])
def test_request_stamps_in_order(stack, tmp_path, prefetch):
    _, reqs, _, _ = _serve_traced(stack, tmp_path, prefetch)
    for r in reqs:
        stamps = [r.submitted_at, r.launched_at, r.prompt_at,
                  r.first_token_at]
        assert None not in stamps, r.uid
        assert stamps == sorted(stamps), r.uid


def test_stamps_use_the_engine_clock(stack):
    g, pipe, cfg, params = stack
    clock = {"t": 100.0}
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=SLOTS, cache_len=CACHE_LEN), now_fn=lambda: clock["t"])
    (r,) = _requests(g)[:1]
    eng.submit(r)
    clock["t"] = 101.0
    (out,) = eng.run_to_completion()
    assert out.submitted_at == 100.0
    assert out.launched_at == out.prompt_at == out.first_token_at == 101.0


@pytest.mark.parametrize("mode,arm", [("compact", "csr"), ("dense", "dense")])
def test_subgraph_span_names_its_gather(stack, tmp_path, mode, arm):
    """``rgl.retrieval.subgraph`` carries the per-hop gather and its width."""
    g, pipe, _, _ = stack
    conf = dataclasses.replace(pipe.config, retrieval_mode=mode)
    pipe = dataclasses.replace(pipe, config=conf)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(pipe.retrieve(jnp.asarray(g.node_feat[:3])).sub)
    finally:
        jax.profiler.stop_trace()
    (sub,) = [s for s in _read_spans(tmp_path) if s[0] == "retrieval.subgraph"]
    want = gr.hop_gather(pipe.graph, conf.k_seeds, conf.strategy, mode=mode,
                         workset_cap=conf.workset_cap,
                         max_nodes=conf.max_nodes)
    assert want[0] == arm
    assert (sub[3]["gather"], sub[3]["cand"]) == want


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile served from the persistent cache is no backend compile."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _delta(before: dict) -> dict:
    now = tracing.compiles()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def test_compiles_counts_a_new_prefill_bucket_once_under_prefill():
    # a config no other test compiles, so every program here is new
    cfg = TransformerConfig(
        name="tracing-compiles", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_head=8, d_ff=32, vocab=40, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(1), cfg)
    with _no_persistent_cache():
        eng = ServeEngine(params, cfg, slots=2, cache_len=64)

        def serve(n_prompt: int, uid: int) -> dict:
            before = tracing.compiles()
            eng.submit(Request(uid=uid, max_new_tokens=2,
                               prompt_ids=np.arange(n_prompt) % 40))
            eng.run_to_completion()
            return _delta(before)

        serve(5, 0)  # bucket 8: the first prefill, merge and decode step
        assert serve(6, 1) == {}  # bucket 8 again
        assert serve(12, 2) == {"prefill": 1}  # bucket 16: one new program
        assert serve(13, 3) == {}


def test_gc_runs_inside_a_span(tmp_path):
    import gc

    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    gcs = [s for s in _read_spans(tmp_path) if s[0] == "gc"]
    assert any(s[3].get("generation") == 2 for s in gcs)
