"""Workset-compacted subgraph construction: dense-parity, overflow
semantics, and workset invariants.

The compact backend's contract: whenever no query overflows the capacity,
its output — nodes, mask, dist, including tie order — is bitwise identical
to the dense backend for every strategy; on overflow the truncation is
deterministic (first-C of the ball ordered by (hop distance, node id)) and
the per-query flag is raised.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph_retrieval as gr
from repro.core import naive
from repro.core.workset import (
    CSRGather, _seed_workset, build_workset, csr_gather, workset_adjacency,
)
from repro.graph import CSRGraph, DeltaGraph, csr_to_ell, generators
from repro.kernels.frontier_expand import ops as fe_ops

STRAT_KW = {
    "bfs": dict(max_hops=3, max_nodes=40),
    "dense": dict(max_hops=2, max_nodes=24),
    "steiner": dict(max_hops=4, max_nodes=64),
    "ppr": dict(max_nodes=40, n_iter=6),
}


@pytest.fixture(scope="module")
def graph():
    g = generators.citation_graph(300, avg_deg=6, seed=7, with_text=False)
    return g, csr_to_ell(g), g.to_adj_dict()


def _seeds(n, q=6, s=4, seed=0):
    return np.random.default_rng(seed).integers(0, n, size=(q, s)).astype(np.int32)


def _assert_bitwise_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.nodes), np.asarray(b.nodes))
    np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(b.mask))
    np.testing.assert_array_equal(np.asarray(a.dist), np.asarray(b.dist))


# -------------------------------------------------------- dense parity ------
@pytest.mark.parametrize("strategy", sorted(gr.STRATEGIES))
def test_compact_matches_dense_generous_cap(graph, strategy):
    """cap >= n: overflow is impossible, outputs must be bitwise equal."""
    g, ell, _ = graph
    seeds = jnp.asarray(_seeds(g.num_nodes))
    dense = gr.STRATEGIES[strategy](ell.nbr, ell.nbr_mask, seeds,
                                    **STRAT_KW[strategy])
    comp = gr.COMPACT_STRATEGIES[strategy](
        ell.nbr, ell.nbr_mask, seeds, workset_cap=512, **STRAT_KW[strategy]
    )
    assert not np.asarray(comp.overflow).any()
    _assert_bitwise_equal(dense, comp)


@pytest.mark.parametrize("strategy", sorted(gr.STRATEGIES))
def test_compact_matches_dense_tight_nonoverflowing_cap(graph, strategy):
    """cap < n but >= every ball: parity must still be exact."""
    g, ell, _ = graph
    seeds = jnp.asarray(_seeds(g.num_nodes, q=4, seed=3))
    kw = dict(STRAT_KW[strategy])
    if strategy in ("bfs", "steiner"):
        kw["max_hops"] = 2  # keep the ball well under the cap
    if strategy == "ppr":
        kw["n_iter"] = 2
    comp = gr.COMPACT_STRATEGIES[strategy](
        ell.nbr, ell.nbr_mask, seeds, workset_cap=256, **kw
    )
    assert not np.asarray(comp.overflow).any(), "cap too tight for this test"
    dense = gr.STRATEGIES[strategy](ell.nbr, ell.nbr_mask, seeds, **kw)
    _assert_bitwise_equal(dense, comp)


def test_retrieve_subgraph_mode_dispatch(graph):
    g, ell, _ = graph
    seeds = _seeds(g.num_nodes, q=3)
    d = gr.retrieve_subgraph(ell, seeds, "bfs", mode="dense",
                             max_hops=2, max_nodes=16)
    c = gr.retrieve_subgraph(ell, seeds, "bfs", mode="compact",
                             workset_cap=512, max_hops=2, max_nodes=16)
    a = gr.retrieve_subgraph(ell, seeds, "bfs", mode="auto",
                             max_hops=2, max_nodes=16)
    assert d.overflow is None  # dense backend does not track overflow
    assert c.overflow is not None
    _assert_bitwise_equal(d, c)
    _assert_bitwise_equal(d, a)  # auto on a small graph = dense
    with pytest.raises(ValueError):
        gr.retrieve_subgraph(ell, seeds, "bfs", mode="nope")


@pytest.mark.parametrize("trial", range(3))
def test_compact_parity_random_graphs(trial):
    """Random (non-PA) graphs, all strategies, through the dispatcher."""
    rng = np.random.default_rng(100 + trial)
    n = int(rng.integers(60, 200))
    src = rng.integers(0, n, size=n * 3)
    dst = rng.integers(0, n, size=n * 3)
    g = CSRGraph.from_edges(src, dst, n, symmetrize=True)
    ell = csr_to_ell(g)
    seeds = rng.integers(0, n, size=(3, 3)).astype(np.int32)
    for strategy in sorted(gr.STRATEGIES):
        kw = dict(STRAT_KW[strategy], max_nodes=min(32, n))
        d = gr.retrieve_subgraph(ell, seeds, strategy, mode="dense", **kw)
        c = gr.retrieve_subgraph(ell, seeds, strategy, mode="compact",
                                 workset_cap=max(256, n), **kw)
        assert not np.asarray(c.overflow).any()
        _assert_bitwise_equal(d, c)


# ------------------------------------------------------ workset invariants --
def test_workset_is_exact_ball_without_overflow(graph):
    g, ell, adj = graph
    seeds = jnp.asarray(_seeds(g.num_nodes, q=4, seed=5))
    ws = build_workset(ell.nbr, ell.nbr_mask, seeds, max_hops=3, cap=512)
    assert not np.asarray(ws.overflow).any()
    ids = np.asarray(ws.ids)
    dist = np.asarray(ws.dist)
    for qi in range(4):
        ball = naive.bfs_distances(
            adj, sorted(set(np.asarray(seeds)[qi].tolist())), 3
        )
        real = ids[qi][ids[qi] < g.num_nodes]
        assert (np.diff(real) > 0).all()  # sorted, unique
        assert set(real.tolist()) == set(ball)
        for v, dv in zip(ids[qi], dist[qi]):
            if v < g.num_nodes:
                assert ball[int(v)] == int(dv)


def test_workset_overflow_truncation_is_deterministic(graph):
    """Truncated workset == first-cap of the ball by (dist, id), flag set."""
    g, ell, adj = graph
    seeds = jnp.asarray(_seeds(g.num_nodes, q=4, seed=9))
    cap = 48
    ws = build_workset(ell.nbr, ell.nbr_mask, seeds, max_hops=3, cap=cap)
    ws2 = build_workset(ell.nbr, ell.nbr_mask, seeds, max_hops=3, cap=cap)
    np.testing.assert_array_equal(np.asarray(ws.ids), np.asarray(ws2.ids))
    np.testing.assert_array_equal(np.asarray(ws.dist), np.asarray(ws2.dist))
    ids = np.asarray(ws.ids)
    dist = np.asarray(ws.dist)
    for qi in range(4):
        ball = naive.bfs_distances(
            adj, sorted(set(np.asarray(seeds)[qi].tolist())), 3
        )
        expect_overflow = len(ball) > cap
        assert bool(np.asarray(ws.overflow)[qi]) == expect_overflow
        want = sorted(ball.items(), key=lambda kv: (kv[1], kv[0]))[:cap]
        got = sorted(
            (int(v), int(dv)) for v, dv in zip(ids[qi], dist[qi])
            if v < g.num_nodes
        )
        assert got == sorted(want)


def test_overflowing_retrieval_is_deterministic_and_flagged(graph):
    g, ell, _ = graph
    seeds = _seeds(g.num_nodes, q=4, seed=2)
    a = gr.retrieve_subgraph(ell, seeds, "bfs", mode="compact",
                             workset_cap=48, max_hops=3, max_nodes=32)
    b = gr.retrieve_subgraph(ell, seeds, "bfs", mode="compact",
                             workset_cap=48, max_hops=3, max_nodes=32)
    assert np.asarray(a.overflow).any()
    _assert_bitwise_equal(a, b)


def test_auto_mode_falls_back_to_dense_on_overflow(graph, monkeypatch):
    """auto + overflow -> transparent dense re-run (flagless exact output)."""
    g, ell, _ = graph
    monkeypatch.setattr(gr, "AUTO_COMPACT_MIN_NODES", 1)
    seeds = _seeds(g.num_nodes, q=4, seed=2)
    sub = gr.retrieve_subgraph(ell, seeds, "bfs", mode="auto",
                               workset_cap=48, max_hops=3, max_nodes=32)
    dense = gr.retrieve_subgraph(ell, seeds, "bfs", mode="dense",
                                 max_hops=3, max_nodes=32)
    assert sub.overflow is None  # the dense re-run is what came back
    _assert_bitwise_equal(sub, dense)


def test_auto_mode_is_traceable_under_outer_jit(graph, monkeypatch):
    """Inside jax.jit the overflow flags are tracers: the host-side
    fallback check must be skipped, not crash with a ConcretizationError."""
    import jax

    g, ell, _ = graph
    monkeypatch.setattr(gr, "AUTO_COMPACT_MIN_NODES", 1)
    seeds = jnp.asarray(_seeds(g.num_nodes, q=3, seed=6))

    @jax.jit
    def traced(s):
        sub = gr.retrieve_subgraph(ell, s, "bfs", mode="auto",
                                   workset_cap=256, max_hops=1, max_nodes=16)
        return sub.nodes, sub.overflow

    nodes, ovf = traced(seeds)
    eager = gr.retrieve_subgraph(ell, seeds, "bfs", mode="compact",
                                 workset_cap=256, max_hops=1, max_nodes=16)
    np.testing.assert_array_equal(np.asarray(nodes), np.asarray(eager.nodes))
    np.testing.assert_array_equal(np.asarray(ovf), np.asarray(eager.overflow))


def test_auto_mode_keeps_ppr_dense(graph, monkeypatch):
    """ppr's n_iter-hop radius overflows practical caps: auto stays dense."""
    g, ell, _ = graph
    monkeypatch.setattr(gr, "AUTO_COMPACT_MIN_NODES", 1)
    seeds = _seeds(g.num_nodes, q=3, seed=6)
    sub = gr.retrieve_subgraph(ell, seeds, "ppr", mode="auto",
                               workset_cap=48, max_nodes=16)
    assert sub.overflow is None  # dense backend ran


def test_workset_adjacency_matches_graph(graph):
    g, ell, adj = graph
    seeds = jnp.asarray(_seeds(g.num_nodes, q=3, seed=4))
    ws = build_workset(ell.nbr, ell.nbr_mask, seeds, max_hops=2, cap=256)
    wnbr, wmask = workset_adjacency(ell.nbr, ell.nbr_mask, ws.ids)
    ids = np.asarray(ws.ids)
    wn, wm = np.asarray(wnbr), np.asarray(wmask)
    for qi in range(3):
        members = {int(v): i for i, v in enumerate(ids[qi]) if v < g.num_nodes}
        for v, i in members.items():
            got = {int(ids[qi][p]) for p, ok in zip(wn[qi, i], wm[qi, i]) if ok}
            expect = {w for w in adj[v] if w in members}
            assert got == expect, (qi, v)


def test_filter_preserves_overflow_flags(graph):
    from repro.core.filters import dynamic_filter, similarity_scores

    g, ell, _ = graph
    seeds = _seeds(g.num_nodes, q=4, seed=2)
    sub = gr.retrieve_subgraph(ell, seeds, "bfs", mode="compact",
                               workset_cap=48, max_hops=3, max_nodes=32)
    emb = jnp.asarray(g.node_feat)
    scores = similarity_scores(emb, emb[seeds[:, 0]])
    out = dynamic_filter(sub, scores, jnp.asarray(seeds), budget=8)
    np.testing.assert_array_equal(
        np.asarray(out.overflow), np.asarray(sub.overflow)
    )


# ------------------------------------------------- CSR vs ELL hop gather ----
def _power_law_graph(seed, n=500, m=1200, alpha=1.25):
    """Chung-Lu style: endpoints drawn with weight (i+1)**-alpha, so a few
    hubs reach degrees 50-100x the mean."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    perm = rng.permutation(n)  # hubs at random ids, not at 0..k
    src = perm[rng.choice(n, size=m, p=w / w.sum())]
    dst = rng.integers(0, n, size=m)
    pairs = np.unique(np.sort(np.stack([src, dst], 1), 1), axis=0)
    pairs = pairs[pairs[:, 0] != pairs[:, 1]]
    return CSRGraph.from_edges(pairs[:, 0], pairs[:, 1], n, symmetrize=True)


def _regular_graph(n=300, half=4):
    """Circulant graph: every node has the same 2*half = 8 neighbors."""
    u = np.repeat(np.arange(n), half)
    v = (u + np.tile(np.arange(1, half + 1), n)) % n
    return CSRGraph.from_edges(u, v, n, symmetrize=True)


GATHER_GRAPHS = {
    "powerlaw": lambda: csr_to_ell(_power_law_graph(11)),
    "powerlaw_truncated": lambda: csr_to_ell(_power_law_graph(12), max_deg=16),
    "regular": lambda: csr_to_ell(_regular_graph()),
}
GATHER_HOPS = 3
GATHER_KW = {
    "bfs": dict(max_hops=GATHER_HOPS, max_nodes=16),
    "dense": dict(max_hops=GATHER_HOPS, max_nodes=16),
    "steiner": dict(max_hops=GATHER_HOPS, max_nodes=16),
    "ppr": dict(n_iter=GATHER_HOPS, max_nodes=16),
}


@pytest.fixture(scope="module", params=sorted(GATHER_GRAPHS))
def gather_graph(request):
    return request.param, GATHER_GRAPHS[request.param]()


def _ell_adj(ell):
    nbr, msk = np.asarray(ell.nbr), np.asarray(ell.nbr_mask)
    return {u: nbr[u][msk[u]].tolist() for u in range(ell.num_nodes)}


def _ball_sizes(ell, seeds, hops):
    """(Q, hops + 1) ball sizes by radius, following the ELL's rows."""
    adj = _ell_adj(ell)
    out = np.zeros((len(seeds), hops + 1), int)
    for qi, row in enumerate(seeds):
        d = np.array(list(naive.bfs_distances(
            adj, sorted(set(row.tolist())), hops).values()))
        out[qi] = [(d <= h).sum() for h in range(hops + 1)]
    return out


def _cap_for(ell, seeds, overflow_at):
    """A workset cap under which some query first overflows at hop
    ``overflow_at`` (0: none does)."""
    balls = _ball_sizes(ell, seeds, GATHER_HOPS)
    if overflow_at == 0:
        return int(balls[:, -1].max())
    cap = max(16, int(balls[:, overflow_at - 1].max()))
    assert (balls[:, overflow_at] > cap).any(), "no query overflows there"
    return cap


def _forced_csr(ell, cap):
    """The CSR gather at one width for every hop, whatever it costs."""
    e = -(-ell.csr.top_degree_sum(cap) // 128) * 128
    return CSRGather(ell.csr.indptr, ell.csr.indices, (max(128, e),))


@pytest.mark.parametrize("overflow_at", [0, 2, 3],
                         ids=["no_overflow", "overflow_hop2", "overflow_hop3"])
@pytest.mark.parametrize("strategy", sorted(gr.COMPACT_STRATEGIES))
def test_csr_gather_matches_ell_gather(gather_graph, strategy, overflow_at):
    """The CSR hop gather gives the ELL gather's workset and subgraph bit
    for bit; it is chosen on skewed graphs and not on a regular one."""
    name, ell = gather_graph
    seeds = jnp.asarray(_seeds(ell.num_nodes, q=6, s=4, seed=21))
    cap = _cap_for(ell, np.asarray(seeds), overflow_at)
    chosen = csr_gather(ell, cap, seeds.shape[1])
    assert (chosen is None) == (name == "regular")
    arms = [None, _forced_csr(ell, cap)] + ([chosen] if chosen else [])
    ws = [build_workset(ell.nbr, ell.nbr_mask, seeds, max_hops=GATHER_HOPS,
                        cap=cap, csr=a) for a in arms]
    subs = [gr.COMPACT_STRATEGIES[strategy](
        ell.nbr, ell.nbr_mask, seeds, workset_cap=cap, csr=a,
        **GATHER_KW[strategy]) for a in arms]
    assert bool(np.asarray(ws[0].overflow).any()) == (overflow_at > 0)
    for w, sub in zip(ws[1:], subs[1:]):
        for f in ("ids", "dist", "overflow"):
            np.testing.assert_array_equal(
                np.asarray(getattr(w, f)), np.asarray(getattr(ws[0], f)), f)
        _assert_bitwise_equal(sub, subs[0])
        np.testing.assert_array_equal(np.asarray(sub.overflow),
                                      np.asarray(subs[0].overflow))


@pytest.mark.parametrize("max_deg", [None, 16], ids=["full", "truncated"])
def test_csr_view_holds_the_kept_edges(max_deg):
    ell = csr_to_ell(_power_law_graph(13), max_deg=max_deg)
    nbr, msk = np.asarray(ell.nbr), np.asarray(ell.nbr_mask)
    indptr, indices = np.asarray(ell.csr.indptr), np.asarray(ell.csr.indices)
    assert indices.size == msk.sum()
    for u in range(ell.num_nodes):
        np.testing.assert_array_equal(indices[indptr[u]:indptr[u + 1]],
                                      nbr[u][msk[u]])


@pytest.mark.parametrize("name", ["powerlaw", "powerlaw_truncated"])
def test_csr_width_bounds_every_hop(name):
    """The widest E is the sum of the C largest degrees, rounded up to 128,
    and no hop proposes more real neighbors than its own width."""
    ell = GATHER_GRAPHS[name]()
    seeds = jnp.asarray(_seeds(ell.num_nodes, q=6, s=4, seed=22))
    deg = np.asarray(ell.degrees())
    degs = np.sort(deg)[::-1]
    chosen = 0
    for cap in (16, 64, 256):
        e = max(128, -(-int(degs[:cap].sum()) // 128) * 128)
        g = csr_gather(ell, cap, seeds.shape[1])
        if g is None:  # E no narrower than the ELL gather's C*K
            assert e >= cap * ell.max_deg
            continue
        chosen += 1
        assert g.width == e
        assert g.widths[0] == max(128, -(-int(degs[:4].sum()) // 128) * 128)
        wi, wd, _ = _seed_workset(seeds, ell.num_nodes, cap)
        for h in range(GATHER_HOPS):
            width = g.widths[min(h, len(g.widths) - 1)]
            real = max(deg[r[r < ell.num_nodes]].sum() for r in np.asarray(wi))
            assert real <= width, (cap, h)
            wi, wd, _, _ = fe_ops.expand_hop(
                wi, wd, ell.nbr, ell.nbr_mask, h + 1, band=GATHER_HOPS + 2,
                csr=(g.indptr, g.indices), width=width)
    assert chosen


def test_merged_delta_view_takes_the_ell_gather():
    """A mutation store's merged view has no CSR view: the ELL gather runs,
    and the compact backend still matches the dense one on it."""
    ell = csr_to_ell(_power_law_graph(14, n=200, m=500))
    d = DeltaGraph(np.asarray(ell.nbr), np.asarray(ell.nbr_mask),
                   ell.num_nodes, ell.num_nodes + 8, extra_deg=4)
    v = int(np.asarray(ell.nbr)[0, 0])
    for a, b in ((3, 150), (150, 3)):  # the graph stays symmetric
        d.add_edge(a, b)
    for a, b in ((0, v), (v, 0)):
        d.del_edge(a, b)
    merged = d.merged()
    assert merged.csr is None
    seeds = _seeds(ell.num_nodes, q=4, seed=23)
    kw = dict(max_hops=2, max_nodes=16)
    arm = gr.hop_gather(merged, 4, "bfs", mode="compact", workset_cap=512,
                        **kw)
    assert arm == ("ell", 512 * merged.max_deg)
    comp = gr.retrieve_subgraph(merged, seeds, "bfs", mode="compact",
                                workset_cap=512, **kw)
    dense = gr.retrieve_subgraph(merged, seeds, "bfs", mode="dense", **kw)
    assert not np.asarray(comp.overflow).any()
    _assert_bitwise_equal(comp, dense)


def test_hop_gather_names_the_arm(gather_graph):
    name, ell = gather_graph
    kw = dict(mode="compact", workset_cap=64, max_nodes=16)
    arm, width = gr.hop_gather(ell, 4, "bfs", **kw)
    if name == "regular":
        assert (arm, width) == ("ell", 64 * ell.max_deg)
    else:
        assert (arm, width) == ("csr", csr_gather(ell, 64, 4).width)
    assert gr.hop_gather(ell, 4, "bfs", mode="dense") == \
        ("dense", ell.num_nodes * ell.max_deg)
