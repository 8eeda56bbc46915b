"""The benchmark's own corpus generator, cached on disk.

A copy of the preferential-attachment citation graph the program ships as
``repro.graph.generators.citation_graph`` (an OGBN-Arxiv stand-in with
community structure in both features and texts), kept here so that no
later change to the program can move the data the benchmark measures on.
Same algorithm, same random stream: for equal arguments it yields the same
edges, features and texts as the program's generator.

The generator's attachment loop is pure Python (about 20 s at 169,343
nodes), so its output is cached under ``bench/.corpus/`` keyed by every
argument that defines it.  Texts are stored as word ids.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path

import numpy as np

CACHE_DIR = Path(__file__).resolve().parent / ".corpus"
# bump when the generator below changes what it produces
GENERATOR_VERSION = 1

WORDS = (
    "graph retrieval neural network attention model learning deep node edge "
    "embedding transformer language token subgraph query index semantic sparse "
    "dense steiner bfs traversal augmented generation context citation paper "
    "abstract method result dataset feature structure efficient scalable"
).split()


@dataclasses.dataclass
class Corpus:
    src: np.ndarray  # (E,) int64 citing node
    dst: np.ndarray  # (E,) int64 cited node (edges are undirected)
    feat: np.ndarray  # (N, D) float32 node features (the embeddings served)
    words: np.ndarray  # (N, text_len) uint8 ids into WORDS

    @property
    def num_nodes(self) -> int:
        return int(self.feat.shape[0])

    def texts(self) -> list:
        vocab = np.asarray(WORDS, dtype=object)
        return [" ".join(row) for row in vocab[self.words]]


def _topic_words(rng, comm, length: int, k: int) -> np.ndarray:
    n_words = len(WORDS)
    probs = np.full((k, n_words), 1.0)
    for c in range(k):
        topic = rng.choice(n_words, size=n_words // k, replace=False)
        probs[c, topic] = 12.0
    probs /= probs.sum(axis=1, keepdims=True)
    out = np.empty((len(comm), length), np.uint8)
    for i, c in enumerate(comm):
        out[i] = rng.choice(n_words, size=length, p=probs[int(c)])
    return out


def generate(n: int, avg_deg: int, d_feat: int, seed: int,
             text_len: int = 24, communities: int = 8) -> Corpus:
    """Preferential-attachment citation network with community features."""
    rng = np.random.default_rng(seed)
    m = max(1, avg_deg // 2)
    src, dst = [], []
    targets = list(range(min(m, n)))
    for v in range(m, n):
        choice = rng.choice(len(targets), size=m, replace=True)
        for c in choice:
            src.append(v)
            dst.append(targets[c])
        targets.extend([v] * m)
        targets.extend([targets[c] for c in choice])
    feat = rng.standard_normal((n, d_feat)).astype(np.float32)
    centers = rng.standard_normal((communities, d_feat)).astype(np.float32) * 2.0
    comm = rng.integers(0, communities, size=n)
    feat += centers[comm]
    words = _topic_words(rng, comm, text_len, communities)
    return Corpus(src=np.asarray(src, np.int64), dst=np.asarray(dst, np.int64),
                  feat=feat, words=words)


def corpus_key(spec: dict) -> str:
    """Cache key over everything that defines the corpus."""
    blob = json.dumps({"version": GENERATOR_VERSION, **spec}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:20]


def load(spec: dict, cache_dir: Path = CACHE_DIR) -> Corpus:
    """The corpus named by ``spec`` (the configuration's ``corpus`` block:
    ``nodes``, ``avg_deg``, ``feat_dim``, ``seed``), from the cache or
    generated and cached."""
    args = dict(n=int(spec["nodes"]), avg_deg=int(spec["avg_deg"]),
                d_feat=int(spec["feat_dim"]), seed=int(spec["seed"]))
    path = Path(cache_dir) / f"citation-{corpus_key(args)}.npz"
    if path.exists():
        with np.load(path) as z:
            return Corpus(src=z["src"], dst=z["dst"], feat=z["feat"],
                          words=z["words"])
    c = generate(**args)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".partial.npz")
    np.savez(tmp, src=c.src, dst=c.dst, feat=c.feat, words=c.words)
    os.replace(tmp, path)
    return c
