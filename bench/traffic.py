"""One general traffic generator, driven by a mix's data file.

A mix file (``bench/traffic/<name>.json``) states:

* ``loop`` -- ``"open"`` (arrivals on a schedule: ``rate_rps``, Poisson) or
  ``"closed"`` (``clients``, each sending its next request when the last
  one came back);
* ``output`` -- output tokens per request: ``{"dist": "fixed", "tokens"}``
  or ``{"dist": "lognormal", "median", "sigma", "min", "max"}``;
* ``queries`` -- ``{"kind": "unique", "noise"}`` (a node's embedding plus
  Gaussian noise, no node twice) or ``{"kind": "zipf", "s", "distinct",
  "noise"}`` (Zipf-popular draws over a fixed set of query vectors, each
  repeat exact);
* ``shape_seed`` -- the seed of the *sizes*: output lengths, inter-arrival
  gaps and popularity ranks are drawn from it in one fixed order, so every
  run does the same work at the same times.  The run's ``--seed`` only
  picks which nodes the queries come from, and their noise.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
import json

import numpy as np

# requests drawn at a time; a run that needs more draws another block
BLOCK = 4096
# substream tags of the run seed
_QUERIES, _WARMUP = 1, 3


@dataclasses.dataclass
class Spec:
    uid: int
    node: int  # the corpus node the query was made from
    query: np.ndarray  # (D,) float32
    max_new: int
    due: float = 0.0  # seconds from window start (open loop)


def load_mix(path) -> dict:
    return json.loads(Path(path).read_text())


def _sizes(mix: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    o = mix["output"]
    if o["dist"] == "fixed":
        return np.full(n, int(o["tokens"]), np.int64)
    if o["dist"] == "lognormal":
        x = rng.lognormal(np.log(o["median"]), o["sigma"], n)
        return np.clip(np.rint(x), o["min"], o["max"]).astype(np.int64)
    raise ValueError(f"unknown output dist {o['dist']!r}")


class Stream:
    """The request stream of one mix under one run seed."""

    def __init__(self, mix: dict, feat: np.ndarray, seed: int,
                 warmup: bool = False):
        self.mix = mix
        self.feat = feat
        self.q_rng = np.random.default_rng([seed, _WARMUP if warmup
                                            else _QUERIES])
        self.shape = np.random.default_rng(int(mix["shape_seed"]))
        q = mix["queries"]
        self.noise = float(q["noise"])
        self.fixed = None
        if q["kind"] == "zipf":
            m = int(q["distinct"])
            p = 1.0 / np.arange(1, m + 1) ** float(q["s"])
            self.popularity = p / p.sum()
            nodes = self.q_rng.choice(len(feat), size=m, replace=False)
            self.fixed = (nodes, self._noisy(nodes))
        elif q["kind"] != "unique":
            raise ValueError(f"unknown query kind {q['kind']!r}")
        self.used = set()
        self.uid = 0
        self.t = 0.0
        self._buf: list = []

    def _noisy(self, nodes):
        noise = self.q_rng.standard_normal((len(nodes), self.feat.shape[1]))
        return (self.feat[nodes] + self.noise * noise).astype(np.float32)

    def _block(self) -> list:
        sizes = _sizes(self.mix, self.shape, BLOCK)
        due = np.zeros(BLOCK)
        if self.mix["loop"] == "open":
            gaps = self.shape.exponential(1.0 / float(self.mix["rate_rps"]),
                                          BLOCK)
            due = self.t + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
            self.t += float(gaps.sum())
        if self.fixed is not None:
            ranks = self.shape.choice(len(self.popularity), size=BLOCK,
                                      p=self.popularity)
            nodes, queries = self.fixed[0][ranks], self.fixed[1][ranks]
        else:
            free = np.setdiff1d(np.arange(len(self.feat)),
                                np.fromiter(self.used, np.int64))
            if len(free) < BLOCK:  # a corpus used up starts over
                self.used.clear()
                free = np.arange(len(self.feat))
            nodes = self.q_rng.choice(free, size=min(BLOCK, len(free)),
                                      replace=False)
            nodes = np.resize(nodes, BLOCK)
            self.used.update(nodes.tolist())
            queries = self._noisy(nodes)
        out = []
        for i in range(BLOCK):
            out.append(Spec(uid=self.uid, node=int(nodes[i]), query=queries[i],
                            max_new=int(sizes[i]), due=float(due[i])))
            self.uid += 1
        return out

    def next(self) -> Spec:
        if not self._buf:
            self._buf = self._block()[::-1]
        return self._buf.pop()

    def take(self, n: int) -> list:
        return [self.next() for _ in range(n)]

    def max_new(self) -> int:
        """The longest output any request of the mix can ask for."""
        o = self.mix["output"]
        return int(o["tokens"] if o["dist"] == "fixed" else o["max"])
