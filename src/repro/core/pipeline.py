"""The end-to-end RGL pipeline (paper Fig. 1): index -> node retrieval ->
graph retrieval -> dynamic filtering -> tokenization -> generation.

``RGLPipeline`` is the OOP API; every stage is also exposed as a composable
function (the paper's Functional API) in its own module, so applications can
re-wire stages (e.g. modality completion stops after ``retrieve``)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import filters, graph_retrieval, node_retrieval, tokenization
from repro.core.graph_retrieval import Subgraph
from repro.graph.ell import ELLGraph


@dataclasses.dataclass
class PipelineConfig:
    strategy: str = "bfs"  # bfs | dense | steiner | ppr
    k_seeds: int = 4
    max_hops: int = 3
    max_nodes: int = 64
    filter_budget: int = 32  # dynamic node filter budget (<= max_nodes)
    max_prompt_len: int = 512
    node_token_budget: int = 48
    # stage-1 vector index: brute | ivf | sharded | sharded_ivf
    index_kind: str = "brute"
    index_shards: Optional[int] = None  # sharded kinds; None = one per device
    # stage-3 subgraph construction backend: dense | compact | auto
    retrieval_mode: str = "auto"
    workset_cap: int = 2048  # compact backend candidate capacity per query


@dataclasses.dataclass(frozen=True)
class RetrievalResult:
    """Typed result of :meth:`RGLPipeline.retrieve` / ``retrieve_many``.

    Replaces the positional ``(sub, seeds, n_valid)`` tuples so the graph
    mutation ``epoch`` has a principled home: the serving cache compares an
    entry's retrieval epoch against the store's current epoch to decide
    whether a collected result may still be cached (see
    :meth:`repro.serving.cache.RetrievalCache.put`).

    ``sub`` keeps the same non-blocking contract as before: it may hold
    in-flight device arrays (or lazy simulation proxies); accessors here
    never force a host sync.
    """

    sub: object  # Subgraph (or a lazy duck-typed stand-in, see simulate.py)
    seeds: object  # (Q, k_seeds) node ids
    n_valid: int = 1  # leading rows of sub/seeds that are meaningful
    epoch: int = 0  # graph mutation epoch the retrieval ran against

    # passthrough views so callers don't reach two levels deep
    @property
    def nodes(self):
        return self.sub.nodes

    @property
    def mask(self):
        return self.sub.mask

    @property
    def dist(self):
        return self.sub.dist

    @property
    def overflow(self):
        return getattr(self.sub, "overflow", None)


def index_from_config(emb, config: PipelineConfig, **kw):
    """Build the stage-1 index named by ``config.index_kind``.

    Serving entry points (``repro.launch.serve``, benchmarks) route through
    this so the index backend and shard count are plain config, not code.
    """
    from repro.core.indexing import build_index

    if config.index_kind in ("sharded", "sharded_ivf"):
        kw.setdefault("n_shards", config.index_shards)
    return build_index(emb, kind=config.index_kind, **kw)


@dataclasses.dataclass
class RGLPipeline:
    graph: ELLGraph
    index: object  # BruteIndex | IVFIndex
    node_emb: jnp.ndarray  # (N, D) embeddings used for filtering scores
    tokenizer: Optional[tokenization.GraphTokenizer] = None
    generator: Optional[object] = None
    node_text: Optional[list] = None
    config: PipelineConfig = dataclasses.field(default_factory=PipelineConfig)
    # Attached by repro.core.mutation.MutableGraphStore.make_pipeline(); a
    # frozen-corpus pipeline leaves it None (epoch stays 0 forever).
    mutation_store: Optional[object] = None

    @property
    def epoch(self) -> int:
        """Monotonic graph mutation epoch this pipeline currently serves."""
        store = self.mutation_store
        return 0 if store is None else int(store.epoch)

    @property
    def n_valid_nodes(self) -> int:
        """Upper bound (exclusive) on node ids a retrieval may return.

        With a mutation store attached the arrays are capacity-padded, so
        the logical node count — not the array length — bounds valid ids.
        """
        store = self.mutation_store
        if store is not None:
            return int(store.n_nodes)
        return int(self.node_emb.shape[0])

    # ---- functional stages --------------------------------------------------
    def retrieve_seeds(self, query_emb, encoder=None):
        return node_retrieval.retrieve_nodes(
            self.index, query_emb, self.config.k_seeds, encoder=encoder
        )

    def retrieve_subgraph(self, seeds) -> Subgraph:
        return graph_retrieval.retrieve_subgraph(
            self.graph,
            seeds,
            self.config.strategy,
            mode=self.config.retrieval_mode,
            workset_cap=self.config.workset_cap,
            max_hops=self.config.max_hops,
            max_nodes=self.config.max_nodes,
        )

    def filter(self, sub: Subgraph, query_emb, seeds) -> Subgraph:
        scores = filters.similarity_scores(self.node_emb, jnp.asarray(query_emb))
        return filters.dynamic_filter(
            sub, scores, jnp.asarray(seeds), budget=self.config.filter_budget
        )

    def retrieve(self, query_emb, encoder=None) -> RetrievalResult:
        """Stages 2+3+filter — the sub-pipeline completion tasks use.  Each
        stage's host dispatch runs in a span of its own; the subgraph's
        names its per-hop gather and width (``graph_retrieval.hop_gather``).
        """
        with tracing.span("retrieval.index"):
            _, seeds = self.retrieve_seeds(query_emb, encoder=encoder)
        c = self.config
        gather, cand = graph_retrieval.hop_gather(
            self.graph, seeds.shape[-1], c.strategy, mode=c.retrieval_mode,
            workset_cap=c.workset_cap, max_nodes=c.max_nodes,
        )
        with tracing.span("retrieval.subgraph", gather=gather, cand=cand):
            sub = self.retrieve_subgraph(seeds)
        with tracing.span("retrieval.filter"):
            sub = self.filter(sub, query_emb, seeds)
        q = jnp.asarray(query_emb)
        n_valid = 1 if q.ndim == 1 else int(q.shape[0])
        return RetrievalResult(sub=sub, seeds=seeds, n_valid=n_valid,
                               epoch=self.epoch)

    def retrieve_many(
        self, query_embs, *, batch_size: Optional[int] = None, encoder=None
    ) -> RetrievalResult:
        """Fixed-shape batched retrieval for serving admission.

        Pads the query batch up to ``batch_size`` rows (zeros) so every
        serving-step admission reuses one jitted retrieval trace regardless of
        how many requests arrived — the paper's amortization mechanism applied
        at serve time.  All retrieval stages are row-independent, so padding
        rows never perturb real results.

        Returns a :class:`RetrievalResult` whose ``sub``/``seeds`` have
        leading dim ``batch_size``; only the first ``n_valid`` rows are
        meaningful.  ``epoch`` records the graph mutation epoch the
        retrieval was dispatched against.

        **Non-blocking contract:** the returned arrays are device arrays whose
        computation may still be in flight (JAX async dispatch) — this method
        never forces a host sync itself.  Callers that need host data must
        ``np.asarray`` the results, which blocks until retrieval finishes; the
        serving prefetch path (:mod:`repro.serving.prefetch`) relies on this
        laziness to overlap wave *i+1*'s retrieval with wave *i*'s decode.
        One caveat: ``retrieval_mode="auto"``'s host-side overflow check in
        :func:`repro.core.graph_retrieval.retrieve_subgraph` forces an early
        sync on the compact backend — prefer ``dense`` or ``compact``
        explicitly when overlap matters.
        """
        q = np.asarray(query_embs, np.float32)
        if q.ndim == 1:
            q = q[None]
        n_valid = q.shape[0]
        bs = batch_size or n_valid
        if n_valid > bs:
            raise ValueError(f"{n_valid} queries > batch_size {bs}")
        if n_valid < bs:
            q = np.concatenate(
                [q, np.zeros((bs - n_valid, q.shape[1]), np.float32)], axis=0
            )
        res = self.retrieve(jnp.asarray(q), encoder=encoder)
        return dataclasses.replace(res, n_valid=n_valid)

    def tokenize(self, query_texts, sub: Subgraph):
        assert self.tokenizer is not None and self.node_text is not None
        texts = tokenization.subgraph_texts(sub, self.node_text)
        return self.tokenizer.batch_linearize(query_texts, texts)

    # ---- OOP API ------------------------------------------------------------
    def run(self, query_emb, query_texts, max_new_tokens: int = 0) -> dict:
        res = self.retrieve(query_emb)
        sub, seeds = res.sub, res.seeds
        ids, mask = self.tokenize(query_texts, sub)
        outputs = None
        if self.generator is not None:
            outputs = self.generator.generate(ids, mask, max_new_tokens)
        return {
            "seeds": np.asarray(seeds),
            "subgraph": sub,
            "prompt_ids": ids,
            "prompt_mask": mask,
            "outputs": outputs,
        }
