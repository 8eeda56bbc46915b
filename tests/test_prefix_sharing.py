"""Prefix-shared paged KV: refcounted block allocator oracle vs a Python
reference model, copy-on-write adoption semantics, double-free/leak
tripwires, cached-prefill reuse parity (engine and RAG tiers, both decode
modes, both admission schedules, contiguous fallback), pool exhaustion
under sharing, and the retrieval-cache pin lifecycle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
    RGLPipeline, Vocab
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    CachedRetrieval, FaultyRetrieval, RAGRequest, RAGServeEngine, Request,
    RetrievalCache, ServeEngine, ServingConfig,
)

CFG = TransformerConfig(
    name="share-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
    d_head=16, d_ff=64, vocab=64, dtype="float32",
)
PARAMS = tm.init_params(jax.random.PRNGKey(0), CFG)


def _assert_mirrors(eng: ServeEngine) -> None:
    """The engine's host allocator mirrors are content-exact replicas of the
    device state: stack contents, per-block refcounts, per-slot tables."""
    depth = len(eng._free_stack)
    assert int(np.asarray(eng.cache.n_free)) == depth
    assert np.asarray(eng.cache.free)[:depth].tolist() == eng._free_stack
    assert np.asarray(eng.cache.ref).tolist() == eng._ref_host.tolist()
    table = np.asarray(eng.cache.table)
    for i, blks in enumerate(eng._slot_blocks):
        assert table[i, :len(blks)].tolist() == blks
        assert (table[i, len(blks):] == -1).all()


def _blank_entry() -> CachedRetrieval:
    z = np.empty(0, np.int32)
    return CachedRetrieval(nodes=z, mask=np.empty(0, bool), dist=z, seeds=z)


# ------------------------------------------------- allocator churn oracle ----
def test_refcount_allocator_churn_oracle():
    """Random alloc/free/acquire/release churn against a plain-Python
    reference allocator: the device free stack (contents, not just depth),
    refcount array, and block tables stay bitwise identical throughout."""
    pool, slots, m, bs = 10, 3, 4, 4
    cache = tm.init_paged_cache(CFG, slots, m * bs, bs, pool)
    ref_free = list(range(pool))
    ref_ref = [0] * pool
    ref_tab = [[] for _ in range(slots)]
    pins = []  # extra holds taken by "cache pins"
    rng = np.random.default_rng(0)

    def check():
        depth = len(ref_free)
        assert int(cache.n_free) == depth
        assert np.asarray(cache.free)[:depth].tolist() == ref_free
        assert np.asarray(cache.ref).tolist() == ref_ref
        tab = np.asarray(cache.table)
        for i in range(slots):
            assert tab[i, :len(ref_tab[i])].tolist() == ref_tab[i]
            assert (tab[i, len(ref_tab[i]):] == -1).all()

    for _ in range(60):
        op = int(rng.integers(0, 4))
        if op == 0:  # grow one slot's table toward a random target
            i = int(rng.integers(slots))
            tgt = int(min(m, len(ref_tab[i]) + rng.integers(0, 3)))
            need = tgt - len(ref_tab[i])
            if need <= 0 or need > len(ref_free):
                continue
            live = np.zeros(slots, bool)
            live[i] = True
            target = np.zeros(slots, np.int32)
            target[i] = tgt
            t, nf, r = tm.alloc_blocks(
                cache.table, cache.free, cache.n_free, cache.ref,
                jnp.asarray(target), jnp.asarray(live), m,
            )
            cache = dataclasses.replace(cache, table=t, n_free=nf, ref=r)
            for _ in range(need):
                b = ref_free.pop()
                ref_ref[b] = 1
                ref_tab[i].append(b)
        elif op == 1:  # retire one slot (drops its holds)
            i = int(rng.integers(slots))
            mask = np.zeros(slots, bool)
            mask[i] = True
            cache = tm.free_slot_blocks(cache, jnp.asarray(mask))
            drops = {}
            for b in ref_tab[i]:
                drops[b] = drops.get(b, 0) + 1
            ref_tab[i] = []
            for b in sorted(drops):  # pushes are ascending-id on device
                ref_ref[b] -= drops[b]
                if ref_ref[b] <= 0:
                    ref_free.append(b)
        elif op == 2:  # pin a random prefix of a held slot's blocks
            i = int(rng.integers(slots))
            if not ref_tab[i]:
                continue
            ids = ref_tab[i][:int(rng.integers(1, len(ref_tab[i]) + 1))]
            cache = tm.acquire_blocks(cache, jnp.asarray(ids, jnp.int32))
            for b in ids:
                ref_ref[b] += 1
            pins.append(list(ids))
        elif pins:  # release a pin
            ids = pins.pop(int(rng.integers(len(pins))))
            cache = tm.release_blocks(cache, jnp.asarray(ids, jnp.int32))
            drops = {}
            for b in ids:
                drops[b] = drops.get(b, 0) + 1
            for b in sorted(drops):
                ref_ref[b] -= drops[b]
                if ref_ref[b] <= 0:
                    ref_free.append(b)
        check()
    # drain everything: the pool must come back whole with zero refs
    for ids in pins:
        cache = tm.release_blocks(cache, jnp.asarray(ids, jnp.int32))
    cache = tm.free_slot_blocks(cache, jnp.asarray(np.ones(slots, bool)))
    assert int(cache.n_free) == pool
    assert (np.asarray(cache.ref) == 0).all()


# --------------------------------------------------------- adoption + COW ----
def test_adopt_prefix_blocks_aliases_full_blocks_and_cows_tail():
    bs, m, pool, L = 4, 4, 8, 10  # nfull=2, partial tail of 2 rows
    cache = tm.init_paged_cache(CFG, 2, m * bs, bs, pool)
    t, nf, r = tm.alloc_blocks(
        cache.table, cache.free, cache.n_free, cache.ref,
        jnp.asarray([3, 0], jnp.int32), jnp.asarray([True, False]), 3,
    )
    cache = dataclasses.replace(cache, table=t, n_free=nf, ref=r)
    donor = np.asarray(t)[0][:3].tolist()
    # write recognizable K/V into the donor's prompt rows
    rows = [b * bs + o for b in donor for o in range(bs)][:L]
    k = np.array(cache.k)  # writable copy
    for pos, row in enumerate(rows):
        k[:, row] = float(pos + 1)
    cache = dataclasses.replace(
        cache,
        k=jnp.asarray(k),
        pos=cache.pos.at[0, :L].set(jnp.arange(L, dtype=jnp.int32)),
        cursor=cache.cursor.at[0].set(L),
    )
    # engine protocol: pin hold (+1) then plan hold (+1) before adoption
    cache = tm.acquire_blocks(cache, jnp.asarray(donor, jnp.int32))
    cache = tm.acquire_blocks(cache, jnp.asarray(donor, jnp.int32))
    src_table = np.full((2, m), -1, np.int32)
    src_table[1, :2] = donor[:2]
    new, cur = tm.adopt_prefix_blocks(
        cache, jnp.zeros(2, jnp.int32), jnp.asarray([False, True]),
        jnp.asarray(src_table), jnp.asarray([0, L], jnp.int32),
        jnp.asarray([-1, donor[2]], jnp.int32),
        jnp.asarray([0, 7], jnp.int32), bs,
    )
    tab1 = np.asarray(new.table)[1]
    assert tab1[:2].tolist() == donor[:2]  # full blocks aliased
    fresh = int(tab1[2])
    assert fresh >= 0 and fresh not in donor  # tail copied, not aliased
    assert tab1[3] == -1
    ref = np.asarray(new.ref)
    # full blocks: donor slot + pin + consumer slot = 3 holds; tail source:
    # the plan's one-dispatch hold was dropped inside adopt -> back to 2
    assert ref[donor[0]] == 3 and ref[donor[1]] == 3
    assert ref[donor[2]] == 2 and ref[fresh] == 1
    # COW copy carried the tail rows bitwise
    np.testing.assert_array_equal(
        np.asarray(new.k)[:, fresh * bs:(fresh + 1) * bs],
        np.asarray(new.k)[:, donor[2] * bs:(donor[2] + 1) * bs],
    )
    pos1 = np.asarray(new.pos)[1]
    assert pos1[:L].tolist() == list(range(L)) and (pos1[L:] == -1).all()
    assert int(np.asarray(new.cursor)[1]) == L
    assert int(np.asarray(cur)[1]) == 7  # donor's recorded first token
    assert int(np.asarray(cur)[0]) == 0  # unmasked slot untouched


# -------------------------------------------- engine-tier sharing + parity ----
def _share_engine(**kw):
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 48)
    kw.setdefault("paged_kv", True)
    kw.setdefault("kv_block_size", 8)
    return ServeEngine(PARAMS, CFG, **kw)


@pytest.mark.parametrize("spec", [False, True])
def test_shared_admission_bitwise_matches_fresh(spec):
    """Donor pins its prefilled prompt blocks to an entry; an identical
    later prompt adopts them and skips prefill — outputs bitwise identical
    to an engine that prefills everything, in both decode modes."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 64, n).astype(np.int32) for n in (13, 16, 9)]

    def run(share):
        eng = _share_engine(prefix_share=share, spec_decode=spec,
                            draft_window=4)
        entries = {}
        outs = {}
        uid = 0
        for wave in range(3):  # each wave re-serves every prompt
            for pi, p in enumerate(prompts):
                e = entries.setdefault(pi, _blank_entry())
                r = Request(uid=uid, prompt_ids=p, max_new_tokens=8)
                if share:
                    r.pin_to = e
                    if e.kv_blocks is not None:
                        r.shared_prefix = e
                eng.submit(r)
                uid += 1
            for r in eng.run_to_completion():
                outs[r.uid] = list(r.out_tokens)
            _assert_mirrors(eng)
        return eng, outs, entries

    ref_eng, ref, _ = run(False)
    sh_eng, got, entries = run(True)
    assert got == ref
    ds = sh_eng.decode_stats()
    assert ds["kv_shared_admits"] >= 6  # waves 2..3 alias all 3 prompts
    assert ds["kv_reused_tokens"] >= 6 * 9
    assert ds["kv_cow_copies"] >= 1  # the 13- and 9-token prompts mid-block
    assert ds["prefill_rows"] < ref_eng.decode_stats()["prefill_rows"]
    assert sh_eng.kv_pins == 3 and sh_eng.kv_pinned_blocks > 0
    # releasing every pin returns the pool to whole, zero refs anywhere
    for e in entries.values():
        e.kv_release(e)
    assert sh_eng._free_host == sh_eng.pool_blocks
    assert (sh_eng._ref_host == 0).all()
    assert (np.asarray(sh_eng.cache.ref) == 0).all()
    _assert_mirrors(sh_eng)


def test_share_plan_falls_back_on_prompt_mismatch():
    """A shared_prefix entry whose pinned prompt differs from the request's
    prompt is re-validated at admission and ignored — fresh prefill, same
    outputs, no shared admits."""
    rng = np.random.default_rng(7)
    pa = rng.integers(1, 64, 12).astype(np.int32)
    pb = rng.integers(1, 64, 12).astype(np.int32)
    assert not np.array_equal(pa, pb)

    eng = _share_engine(prefix_share=True)
    entry = _blank_entry()
    eng.submit(Request(uid=0, prompt_ids=pa, max_new_tokens=6, pin_to=entry))
    eng.run_to_completion()
    assert entry.kv_blocks is not None and entry.kv_len == 12
    # wrong prompt riding the entry: must not alias
    eng.submit(Request(uid=1, prompt_ids=pb, max_new_tokens=6,
                       shared_prefix=entry))
    done = {r.uid: r for r in eng.run_to_completion()}
    ref = _share_engine(prefix_share=False)
    ref.submit(Request(uid=1, prompt_ids=pb, max_new_tokens=6))
    ref_done = {r.uid: r for r in ref.run_to_completion()}
    assert done[1].out_tokens == ref_done[1].out_tokens
    assert eng.kv_shared_admits == 0
    _assert_mirrors(eng)
    entry.kv_release(entry)
    assert eng._free_host == eng.pool_blocks


# ----------------------------------------------------------------- tripwires ----
def test_alloc_guard_raises_with_pool_counters():
    eng = _share_engine()
    assert eng._kv_debug  # conftest arms RGL_KV_DEBUG for the whole suite
    with pytest.raises(RuntimeError, match="alloc invariant"):
        eng._guard_alloc(eng.pool_blocks + 1, "unit test")


def test_double_free_tripwire_raises():
    eng = _share_engine()
    blk = eng._pop_host(0, 1)[0]
    with pytest.raises(RuntimeError, match="double-free"):
        eng._host_release({blk: 2})  # two drops against a single hold


# ------------------------------------------------------- RAG-tier sharing ----
N_NODES = 120
CACHE_LEN = 96
SLOTS = 3


@pytest.fixture(scope="module")
def stack():
    g = generators.citation_graph(N_NODES, avg_deg=6, seed=7)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=64, node_budget=6)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                              max_nodes=16, filter_budget=8),
    )
    cfg = TransformerConfig(
        name="share-rag", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _rag_run(stack, share, src=None, n=12, uniq=4, **kw):
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(src or pipe, params, cfg, config=ServingConfig(
        slots=SLOTS, cache_len=CACHE_LEN, prefix_share=share, **kw))
    q_ids = [u % uniq for u in range(n)]  # repeat-heavy: sharing regime
    for u, qi in enumerate(q_ids):
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[qi]),
                              query_text=g.node_text[qi], max_new_tokens=4))
    done = {r.uid: r for r in eng.drain()}
    outs = {
        u: (list(r.out_tokens),
            np.asarray(r.retrieved_nodes).tolist(),
            np.asarray(r.prompt_ids).tolist())
        for u, r in done.items() if r.done and not r.failed
    }
    return eng, outs


def _assert_share_clean(eng):
    inner = eng.engine
    assert not inner.queue and not inner.live.any()
    if inner.paged_kv:
        _assert_mirrors(inner)
        assert inner._free_host == inner.pool_blocks - inner.kv_pinned_blocks
        # no holder is unaccounted: every remaining ref belongs to a pin
        assert int(inner._ref_host.sum()) == sum(
            np.asarray(s.entry.kv_blocks).size
            for s in eng.cache._data.values()
            if s.entry.kv_blocks is not None
        )
        freed = eng.cache.reclaim_kv(10 ** 9)
        assert freed == 0 or inner._free_host == inner.pool_blocks
        assert inner._free_host == inner.pool_blocks  # zero leaked blocks
        assert (inner._ref_host == 0).all()


@pytest.mark.parametrize("spec,admission", [(False, "wave"),
                                            (True, "continuous")])
def test_rag_prefix_share_parity(stack, spec, admission):
    """The end-to-end acceptance bar: share-on output (out_tokens,
    retrieved_nodes, prompt_ids per uid) is bitwise identical to share-off
    on a repeat-heavy stream, sharing actually fires, and the pool has zero
    leaked blocks after the drain."""
    kw = dict(paged_kv=True, spec_decode=spec, admission=admission)
    _, ref = _rag_run(stack, share=False, **kw)
    eng, got = _rag_run(stack, share=True, **kw)
    assert got == ref
    ds = eng.engine.decode_stats()
    assert ds["kv_shared_admits"] > 0
    assert ds["prefill_rows"] < len(ref)
    assert eng.cache.kv_pinned_entries() > 0
    _assert_share_clean(eng)


def test_rag_prefix_share_contiguous_fallback(stack):
    """prefix_share=True on a contiguous arena is inert: identical outputs,
    no sharing machinery engaged."""
    _, ref = _rag_run(stack, share=False, paged_kv=False)
    eng, got = _rag_run(stack, share=True, paged_kv=False)
    assert got == ref
    assert not eng.engine.prefix_share  # forced off without the paged arena
    assert eng.engine.decode_stats()["prefill_rows"] == len(ref)


def test_pool_exhaustion_under_sharing_truncates_and_recovers(stack):
    """Undersized pool + sharing: cache pins are reclaimed before any live
    request is truncated, every request terminates, outputs match the
    unshared run bitwise, and nothing leaks."""
    kw = dict(paged_kv=True, kv_pool_blocks=8, n=10, uniq=3)
    ref_eng, ref = _rag_run(stack, share=False, **kw)
    eng, got = _rag_run(stack, share=True, **kw)
    assert got == ref
    assert set(got) == set(range(10))  # everything terminated
    assert eng.engine.truncations == ref_eng.engine.truncations
    assert eng.engine.kv_pins > 0  # pinning happened...
    assert eng.engine.kv_releases > 0  # ...and pressure reclaimed pins
    _assert_share_clean(eng)


@pytest.mark.slow
@pytest.mark.parametrize("prefetch", [False, True])
@pytest.mark.parametrize("spec", [False, True])
@pytest.mark.parametrize("admission", ["wave", "continuous"])
def test_rag_prefix_share_parity_matrix(stack, prefetch, spec, admission):
    kw = dict(paged_kv=True, prefetch=prefetch, spec_decode=spec,
              admission=admission)
    _, ref = _rag_run(stack, share=False, **kw)
    eng, got = _rag_run(stack, share=True, **kw)
    assert got == ref
    assert eng.engine.decode_stats()["kv_shared_admits"] > 0
    _assert_share_clean(eng)


@pytest.mark.slow
def test_chaos_soak_with_prefix_sharing(stack):
    """Seeded retrieval chaos with sharing on: the fault-free subset is
    bitwise identical to a clean unshared run, every request reaches a
    terminal state, and the pool shows zero leaked or double-freed blocks
    (RGL_KV_DEBUG is armed suite-wide, so a double-free would raise)."""
    g, pipe, cfg, params = stack
    _, clean = _rag_run(stack, share=False, paged_kv=True, n=14, uniq=7)
    faulty = FaultyRetrieval(pipe, seed=23, fault_rate=0.25)
    bad_q = {qi for qi in range(7)
             if faulty.fault_of(np.asarray(g.node_feat[qi])) is not None}
    eng, got = _rag_run(stack, share=True, src=faulty, n=14, uniq=7,
                        paged_kv=True, max_retries=1,
                        retrieval_timeout_s=0.05)
    assert got  # the fault-free subset completed
    for u, out in got.items():
        if (u % 7) not in bad_q:
            assert out == clean[u]
    _assert_share_clean(eng)


# --------------------------------------------------- cache pin lifecycle ----
def _emb(i):
    return np.full(4, float(i), np.float32)


def _pinned_entry(owner, blocks, released):
    e = _blank_entry()
    e.kv_blocks = np.asarray(blocks, np.int32)
    e.kv_owner = owner

    def rel(entry):
        n = int(np.asarray(entry.kv_blocks).size)
        entry.kv_blocks = None
        entry.kv_release = None
        released.append(entry)
        return n

    e.kv_release = rel
    return e


def test_cache_releases_kv_pin_on_eviction_and_overwrite():
    released = []
    cache = RetrievalCache(capacity=2, policy="lru")
    e0 = _pinned_entry("eng", [1, 2], released)
    e1 = _pinned_entry("eng", [3], released)
    cache.put(_emb(0), e0)
    cache.put(_emb(1), e1)
    assert cache.is_resident(e0) and cache.is_resident(e1)
    cache.put(_emb(2), _blank_entry())  # capacity: evicts e0 (LRU)
    assert released == [e0] and not cache.is_resident(e0)
    # overwrite of a live key releases the displaced entry's pin
    cache.put(_emb(1), _blank_entry())
    assert released == [e0, e1] and not cache.is_resident(e1)
    assert cache.kv_pinned_entries() == 0


def test_cache_ttl_purge_releases_kv_pin_and_counts_expiry_once():
    released = []
    clock = {"t": 0.0}
    cache = RetrievalCache(capacity=2, policy="lru", ttl=1.0,
                           now_fn=lambda: clock["t"])
    e0 = _pinned_entry("eng", [0, 1, 2], released)
    cache.put(_emb(0), e0)
    clock["t"] = 2.0
    assert cache.get(_emb(0)) is None  # expired (counted once)
    assert cache.get(_emb(0)) is None
    assert cache.expired == 1
    assert cache.stats()["resident"] == 1 and cache.stats()["live"] == 0
    cache.put(_emb(1), _blank_entry())
    cache.put(_emb(2), _blank_entry())  # purge reclaims the expired entry
    assert released == [e0]
    assert cache.expired == 1  # purge does not double-count the expiry


def test_reclaim_kv_orders_victims_and_filters_owner():
    released = []
    clock = {"t": 0.0}
    cache = RetrievalCache(capacity=8, policy="lru", ttl=10.0,
                           now_fn=lambda: clock["t"])
    stale = _pinned_entry("eng", [0], released)
    cold = _pinned_entry("eng", [1, 2], released)
    warm = _pinned_entry("eng", [3, 4], released)
    other = _pinned_entry("other-eng", [5], released)
    cache.put(_emb(0), stale)
    clock["t"] = 11.0  # only `stale` is TTL-expired now
    cache.put(_emb(1), cold)
    cache.put(_emb(2), warm)
    cache.put(_emb(3), other)
    assert cache.get(_emb(2)) is warm  # refresh: cold is now least-recent
    # expired pins go first, then LRU order among the rest; other-owner
    # pins are untouched by an owner-filtered reclaim
    freed = cache.reclaim_kv(2, owner="eng")
    assert freed >= 2 and released[0] is stale and released[1] is cold
    assert warm.kv_blocks is not None and other.kv_blocks is not None
    freed = cache.reclaim_kv(100, owner="eng")
    assert released[-1] is warm and other.kv_blocks is not None
    # unfiltered reclaim takes the remaining foreign pin too
    assert cache.reclaim_kv(100) == 1 and other.kv_blocks is None
    # entries keep their retrieval results: only the pins were dropped
    assert len(cache) == 4 and cache.kv_pinned_entries() == 0


def test_pin_gate_rejects_non_resident_entry():
    """The engine consults kv_pin_gate before pinning: an entry that was
    evicted between submit and admission must not be pinned (the pin would
    hold pool blocks no eviction could ever release)."""
    eng = _share_engine(prefix_share=True)
    cache = RetrievalCache(capacity=1, policy="lru")
    eng.kv_pin_gate = cache.is_resident
    evicted = _blank_entry()
    cache.put(_emb(0), evicted)
    cache.put(_emb(1), _blank_entry())  # capacity 1: evicts `evicted`
    assert not cache.is_resident(evicted)
    p = np.arange(1, 13, dtype=np.int32)
    eng.submit(Request(uid=0, prompt_ids=p, max_new_tokens=4,
                       pin_to=evicted))
    eng.run_to_completion()
    assert evicted.kv_blocks is None and eng.kv_pins == 0
    assert eng._free_host == eng.pool_blocks  # nothing held back
