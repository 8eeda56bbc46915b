"""Self-speculative decode: bitwise parity vs one-token decode, EOS-inside-
window, max_new/cache_len truncation clamps, drafter lookup semantics, and
parity through the fused RAG engine under both admission schedules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import Request, ServeEngine, ServingConfig
from repro.serving.drafter import draft_tokens

CFG = TransformerConfig(
    name="spec-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
    d_head=16, d_ff=64, vocab=64, dtype="float32",
)
PARAMS = tm.init_params(jax.random.PRNGKey(0), CFG)


def _mixed_requests(seed=3):
    """Random + repetitive prompts, mixed generation lengths (staggered slot
    turnover), incl. a max_new=1 request (admission-time finish)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for u, mn in enumerate([5, 12, 1, 30, 8, 12, 25]):
        if u % 2:
            pat = rng.integers(1, 64, size=int(rng.integers(2, 4)))
            p = np.tile(pat, 6)[: int(rng.integers(4, 10))]
        else:
            p = rng.integers(1, 64, size=int(rng.integers(3, 10)))
        reqs.append(Request(uid=u, prompt_ids=p.astype(np.int32),
                            max_new_tokens=mn))
    return reqs


def _run(reqs, **kw):
    eng = ServeEngine(PARAMS, CFG, slots=3, cache_len=48, **kw)
    for r in reqs:
        eng.submit(r)
    done = {r.uid: r for r in eng.run_to_completion()}
    return eng, done


# ------------------------------------------------------------------ parity ----
@pytest.mark.parametrize("window", [2, 4, 8])
def test_bitwise_parity_across_windows(window):
    """spec_decode=on emits bitwise-identical out_tokens to one-token decode
    for every request, at every draft window."""
    base_eng, base = _run(_mixed_requests(), spec_decode=False)
    spec_eng, spec = _run(_mixed_requests(), spec_decode=True,
                          draft_window=window)
    assert set(base) == set(spec) == set(range(7))
    for u in base:
        assert spec[u].out_tokens == base[u].out_tokens, f"uid {u}"
    # same tokens, strictly fewer-or-equal decode dispatches
    assert spec_eng.decode_steps <= base_eng.decode_steps
    assert spec_eng.decode_tokens == base_eng.decode_tokens
    ds = spec_eng.decode_stats()
    assert ds["spec_decode"] and ds["draft_window"] == window
    assert ds["tokens_per_step"] >= 1.0


def test_parity_with_sliding_window_attention():
    cfg = TransformerConfig(
        name="spec-sw", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=64, dtype="float32", sliding_window=16,
    )
    params = tm.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(0)
    outs = {}
    for spec in (False, True):
        eng = ServeEngine(params, cfg, slots=2, cache_len=48,
                          spec_decode=spec, draft_window=4)
        r2 = np.random.default_rng(0)
        for u in range(4):
            eng.submit(Request(uid=u,
                               prompt_ids=r2.integers(1, 64, 8).astype(np.int32),
                               max_new_tokens=30))
        outs[spec] = {r.uid: r.out_tokens for r in eng.run_to_completion()}
    assert outs[True] == outs[False]


def test_parity_with_quantized_kv_cache():
    cfg = TransformerConfig(
        name="spec-q", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=64, dtype="float32", kv_quant=True,
    )
    params = tm.init_params(jax.random.PRNGKey(2), cfg)
    outs = {}
    for spec in (False, True):
        eng = ServeEngine(params, cfg, slots=2, cache_len=48,
                          spec_decode=spec, draft_window=4)
        r2 = np.random.default_rng(5)
        for u in range(3):
            eng.submit(Request(uid=u,
                               prompt_ids=r2.integers(1, 64, 6).astype(np.int32),
                               max_new_tokens=20))
        outs[spec] = {r.uid: r.out_tokens for r in eng.run_to_completion()}
    assert outs[True] == outs[False]


def test_parity_matches_offline_greedy():
    """Spec decode == slot engine == offline greedy generation."""
    from repro.models.transformer.generate import generate_tokens

    prompt = np.asarray([5, 9, 3, 22, 41], np.int32)
    eng = ServeEngine(PARAMS, CFG, slots=2, cache_len=32, spec_decode=True,
                      draft_window=4)
    eng.submit(Request(uid=0, prompt_ids=prompt, max_new_tokens=8))
    done = eng.run_to_completion()
    offline = generate_tokens(
        PARAMS, jnp.asarray(prompt)[None], jnp.asarray([len(prompt)]),
        jax.random.PRNGKey(0), CFG, max_new=8, cache_len=32, temperature=0.0,
    )
    assert done[0].out_tokens[:8] == np.asarray(offline[0]).tolist()


# ----------------------------------------------------------- EOS in window ----
def test_eos_inside_window_truncates_exactly():
    """EOS accepted mid-window ends the request at the first EOS, matching
    the one-token schedule bit for bit."""
    # find a token the model actually emits mid-stream, use it as EOS
    _, probe = _run(_mixed_requests(), spec_decode=False)
    eos = None
    for u in probe:
        toks = probe[u].out_tokens
        for t in toks[2:-1]:
            eos = int(t)
            break
        if eos is not None:
            break
    assert eos is not None, "probe stream emitted too few tokens"

    _, base = _run(_mixed_requests(), spec_decode=False, eos_id=eos)
    spec_eng, spec = _run(_mixed_requests(), spec_decode=True,
                          draft_window=8, eos_id=eos)
    for u in base:
        assert spec[u].out_tokens == base[u].out_tokens
        toks = spec[u].out_tokens
        # nothing may be emitted past the first EOS
        if eos in toks:
            assert toks.index(eos) == len(toks) - 1
    # at least one request must actually have stopped on EOS for this test
    # to exercise the path
    assert any(r.out_tokens and r.out_tokens[-1] == eos
               for r in spec.values())


# -------------------------------------------------------- truncation clamps ----
@pytest.mark.parametrize("max_new", [1, 3, 7])
def test_window_never_overshoots_max_new(max_new):
    """Regression: multi-token acceptance must clamp at max_new_tokens even
    when the draft window is larger than the remaining budget (and the old
    append-then-check accounting would have overshot)."""
    pat = np.asarray([11, 27], np.int32)
    for spec in (False, True):
        eng = ServeEngine(PARAMS, CFG, slots=2, cache_len=64,
                          spec_decode=spec, draft_window=8)
        for u in range(4):
            eng.submit(Request(uid=u, prompt_ids=np.tile(pat, 8),
                               max_new_tokens=max_new))
        done = eng.run_to_completion()
        assert len(done) == 4
        for r in done:
            assert len(r.out_tokens) == max_new, \
                f"spec={spec}: emitted {len(r.out_tokens)} != {max_new}"


def test_window_never_overshoots_cache_len():
    """Acceptance must also clamp at the KV arena edge: a window that would
    run past cache_len commits only the tokens that fit."""
    cache_len = 24
    prompt = np.asarray([3, 7, 3, 7, 3, 7, 3, 7], np.int32)  # L=8
    lens = {}
    for spec in (False, True):
        eng = ServeEngine(PARAMS, CFG, slots=1, cache_len=cache_len,
                          spec_decode=spec, draft_window=8)
        eng.submit(Request(uid=0, prompt_ids=prompt, max_new_tokens=1000))
        done = eng.run_to_completion()
        lens[spec] = len(done[0].out_tokens)
        # 1 prefill token + decode up to cursor == cache_len
        assert lens[spec] == cache_len - len(prompt) + 1
    assert lens[True] == lens[False]


def test_max_new_one_finishes_at_admission():
    """max_new_tokens=1 emits exactly the prefill token in both modes (the
    old engine emitted a second token before checking the budget)."""
    for spec in (False, True):
        eng = ServeEngine(PARAMS, CFG, slots=2, cache_len=32,
                          spec_decode=spec)
        eng.submit(Request(uid=0, prompt_ids=np.asarray([4, 9], np.int32),
                           max_new_tokens=1))
        done = eng.run_to_completion()
        assert len(done) == 1 and len(done[0].out_tokens) == 1


# ------------------------------------------------------------------ drafter ----
def test_drafter_bigram_cycle_extrapolation():
    """A locked period-3 loop is drafted exactly, wrapping past the end of
    history: hist [1,2,3,1,2] -> continuation [3,1,2,3]."""
    hist = np.zeros((1, 16), np.int32)
    hist[0, :5] = [1, 2, 3, 1, 2]
    out = np.asarray(draft_tokens(jnp.asarray(hist),
                                  jnp.asarray([5], np.int32), 4))
    assert out[0].tolist() == [3, 1, 2, 3]


def test_drafter_unigram_fallback_and_repeat_last():
    hist = np.zeros((2, 16), np.int32)
    hist[0, :3] = [7, 9, 9]   # unigram match at j=1, period 1 -> all 9s
    hist[1, :3] = [4, 5, 6]   # no match at all -> repeat last token
    out = np.asarray(draft_tokens(jnp.asarray(hist),
                                  jnp.asarray([3, 3], np.int32), 3))
    assert out[0].tolist() == [9, 9, 9]
    assert out[1].tolist() == [6, 6, 6]


def test_drafter_prefers_bigram_over_unigram():
    """The bigram occurrence wins even when a more recent unigram match
    exists: hist [2,5, 9,5, 2,5] trailing bigram (2,5) -> continuation from
    j=1, not from the later unigram 5 at j=3."""
    hist = np.zeros((1, 16), np.int32)
    hist[0, :6] = [2, 5, 9, 5, 2, 5]
    out = np.asarray(draft_tokens(jnp.asarray(hist),
                                  jnp.asarray([6], np.int32), 2))
    assert out[0].tolist() == [9, 5]


def test_drafter_dead_slot_is_harmless():
    hist = np.zeros((1, 8), np.int32)
    out = np.asarray(draft_tokens(jnp.asarray(hist),
                                  jnp.asarray([0], np.int32), 3))
    assert out.shape == (1, 3)  # content irrelevant: verification rejects


# ------------------------------------------------- fused RAG engine parity ----
@pytest.fixture(scope="module")
def rag_stack():
    from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
        RGLPipeline, Vocab
    from repro.graph import csr_to_ell, generators

    g = generators.citation_graph(100, avg_deg=6, seed=11)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=48, node_budget=6)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                              max_nodes=12, filter_budget=6),
    )
    cfg = TransformerConfig(
        name="spec-rag-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def test_rag_engine_parity_across_all_schedules(rag_stack):
    """The full decode/admission schedule matrix — {one-token, speculative}
    x {sync, prefetched admission} — produces bitwise-identical per-request
    outputs, retrievals, and cache accounting (the CI matrix flips the same
    two switches via RGL_SPEC_DECODE / RGL_PREFETCH)."""
    from repro.serving import RAGRequest, RAGServeEngine

    g, pipe, cfg, params = rag_stack

    def run(spec, prefetch):
        eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=2, cache_len=96, prefetch=prefetch, spec_decode=spec,
            draft_window=4))
        q_ids = [0, 1, 2, 0, 3, 1]
        for u, qi in enumerate(q_ids):
            eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[qi]),
                                  query_text=g.node_text[qi],
                                  max_new_tokens=4 + 2 * (u % 3)))
        done = {r.uid: r for r in eng.run_to_completion()}
        assert len(done) == 6
        return eng, done

    ref_eng, ref = run(spec=False, prefetch=False)
    for spec, prefetch in [(False, True), (True, False), (True, True)]:
        eng, done = run(spec, prefetch)
        for u in ref:
            assert done[u].out_tokens == ref[u].out_tokens, (spec, prefetch)
            np.testing.assert_array_equal(done[u].retrieved_nodes,
                                          ref[u].retrieved_nodes)
            np.testing.assert_array_equal(done[u].prompt_ids,
                                          ref[u].prompt_ids)
        assert eng.cache_hits == ref_eng.cache_hits
        assert eng.cache_misses == ref_eng.cache_misses
        s = eng.stats()
        assert s["spec_decode"] == spec and s["prefetch"] == prefetch
        assert s["emitted_tokens"] == ref_eng.stats()["emitted_tokens"]


def test_rag_overlap_telemetry_counts_tokens(rag_stack):
    """Prefetch overlap telemetry reports accepted tokens (schedule-
    invariant work), not just steps: under speculation one step commits
    several tokens, so overlap_tokens >= overlap_steps."""
    import time

    from repro.serving import DelayedRetrieval, RAGRequest, RAGServeEngine

    g, pipe, cfg, params = rag_stack
    eng = RAGServeEngine(
        DelayedRetrieval(pipe, cost_s=0.02), params, cfg,
        config=ServingConfig(slots=2, cache_len=96, prefetch=True,
                             spec_decode=True, draft_window=4))
    for u in range(6):
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                              query_text=g.node_text[u], max_new_tokens=10))
    done = eng.run_to_completion()
    assert len(done) == 6
    s = eng.stats()
    assert s["prefetch_waves"] >= 1
    assert s["overlap_tokens"] >= s["overlap_steps"] >= 1
    # sync schedule accrues no overlap at all
    sync = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=96, prefetch=False, spec_decode=True))
    for u in range(4):
        sync.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                               query_text=g.node_text[u], max_new_tokens=6))
    sync.run_to_completion()
    assert sync.stats()["overlap_tokens"] == 0


# ---------------------------------------------------------- configuration ----
def _config_engine(rag_stack, **kw):
    """The decode engine a RAGServeEngine builds from
    ``ServingConfig(slots=1, cache_len=96, **kw)`` in the current env."""
    from repro.serving import RAGServeEngine

    _, pipe, cfg, params = rag_stack
    return RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=1, cache_len=96, **kw)).engine


def test_spec_env_default_and_override(rag_stack, monkeypatch):
    def make(**kw):
        return _config_engine(rag_stack, **kw)

    monkeypatch.delenv("RGL_SPEC_DECODE", raising=False)
    monkeypatch.delenv("RGL_DRAFT_WINDOW", raising=False)
    assert not ServingConfig.from_env().spec_decode
    assert ServingConfig.from_env().draft_window == 4
    assert not make().spec_decode
    monkeypatch.setenv("RGL_SPEC_DECODE", "1")
    assert ServingConfig.from_env().spec_decode
    assert make().spec_decode
    assert not ServingConfig.resolve(None, spec_decode=False).spec_decode
    assert not make(spec_decode=False).spec_decode  # explicit beats env
    monkeypatch.setenv("RGL_SPEC_DECODE", "0")
    assert not make().spec_decode
    assert make(spec_decode=True).spec_decode
    monkeypatch.setenv("RGL_DRAFT_WINDOW", "6")
    assert ServingConfig.from_env().draft_window == 6
    assert make(spec_decode=True).draft_window == 6
    with pytest.raises(ValueError, match="draft_window"):
        make(spec_decode=True, draft_window=1)


def test_draft_window_env_raises_like_constructor(rag_stack, monkeypatch):
    """RGL_DRAFT_WINDOW=1 must fail exactly like draft_window=1 — it used
    to be silently clamped to 2, so the same invalid input had two
    behaviors depending on which path set it."""
    def make(**kw):
        return _config_engine(rag_stack, **kw)

    monkeypatch.setenv("RGL_DRAFT_WINDOW", "1")
    assert ServingConfig.from_env().draft_window == 1
    with pytest.raises(ValueError, match="draft_window"):
        make(spec_decode=True)
    # non-speculative engines never validate the window (parity with the
    # constructor path, where draft_window=1 is fine if spec is off)
    assert make(spec_decode=False).draft_window == 1
    monkeypatch.setenv("RGL_DRAFT_WINDOW", "banana")
    with pytest.raises(ValueError, match="RGL_DRAFT_WINDOW"):
        ServingConfig.from_env()
    with pytest.raises(ValueError, match="RGL_DRAFT_WINDOW"):
        make(spec_decode=True)


def test_acceptance_telemetry_on_repetitive_stream():
    """A strongly cyclic stream must commit >1 token per slot-step and
    account drafts consistently."""
    pat = np.asarray([13, 29, 44], np.int32)
    eng = ServeEngine(PARAMS, CFG, slots=2, cache_len=96, spec_decode=True,
                      draft_window=4)
    for u in range(4):
        eng.submit(Request(uid=u, prompt_ids=np.tile(pat, 8),
                           max_new_tokens=60))
    done = eng.run_to_completion()
    assert len(done) == 4
    ds = eng.decode_stats()
    assert ds["tokens_per_step"] > 1.2  # speculation actually accepted
    assert ds["draft_accepted"] == ds["decode_tokens"] - eng.slot_steps
    assert 0.0 < ds["draft_accept_rate"] <= 1.0
