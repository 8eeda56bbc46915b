"""Plain references that decide ``correct``.  They import nothing of the
program and take nothing it made.

* :func:`request_gaps` -- served tokens scored by the model family's
  ``lm_logits`` (``bench/models/<architecture>.py``): the decoder's
  forward pass in float32 at ``highest`` matmul precision, written from
  the architecture's equations.  With ``quant=True`` every family's pass
  is the control, one precision below the bfloat16 the configurations
  serve in: each linear layer through :func:`linear`, its weights and
  inputs rounded to float8 e4m3 (scaled per output channel and per token).
* :class:`RetrievalReference` -- index, compact BFS, filter and
  linearization on the host in float64 / exact integers.
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# language model: the float8 control and the served-token comparison
# --------------------------------------------------------------------------
E4M3_MAX = 448.0  # largest finite float8 e4m3 value
E4M3_MIN_EXP = -6  # smallest normal exponent; below it the step is fixed


def _f8(x, axis):
    """Float8 e4m3 rounding along ``axis`` (absmax scaled to 448), as
    float32: three mantissa bits, round half to even, subnormals kept.
    Written in float32 arithmetic so that every backend rounds alike."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    y = x / jnp.maximum(s, 1e-30)
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 1e-30)))
    step = jnp.exp2(jnp.maximum(e, E4M3_MIN_EXP) - 3.0)
    y = jnp.clip(jnp.round(y / step) * step, -E4M3_MAX, E4M3_MAX)
    return y * s


def linear(x, w, quant: bool):
    """``x @ w`` at ``highest`` precision; with ``quant`` the control's
    float8 rounding of both, weights per output channel, inputs per row."""
    if quant:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.matmul(x, w, precision=HI)


@jax.jit
def token_gaps(ref_logits, rows, tokens):
    """How far each served token's reference logit lies below the
    reference's best at its row: (n,) float32."""
    r = ref_logits[rows]
    return jnp.max(r, -1) - jnp.take_along_axis(r, tokens[:, None], 1)[:, 0]


@jax.jit
def control_gaps(ref_logits, ctl_logits, rows):
    """Gap, under the reference, of the token the control puts first."""
    r = ref_logits[rows]
    top = jnp.argmax(ctl_logits[rows], -1)
    return jnp.max(r, -1) - jnp.take_along_axis(r, top[:, None], 1)[:, 0]


def served_rows(prompt_len: int, n_out: int, pad_to: int):
    """(sequence, rows) for scoring ``n_out`` served tokens after a prompt:
    the sequence is prompt + all but the last served token, zero-padded at
    the tail (causal, so padding never reaches a scored row), and row
    ``prompt_len - 1 + i`` predicts served token ``i``."""
    length = prompt_len + n_out - 1
    if length > pad_to:
        raise ValueError(f"sequence of {length} tokens past pad {pad_to}")
    return length, np.arange(prompt_len - 1, prompt_len - 1 + n_out)


def request_gaps(family, params, hp, prompt, out, pad_to: int,
                 quant_control=False):
    """Served-token gaps of one request under ``family``'s reference (and
    the control's gaps at the same rows when ``quant_control``)."""
    out = np.asarray(out, np.int32)
    length, rows = served_rows(len(prompt), len(out), pad_to)
    seq = np.zeros(pad_to, np.int32)
    seq[:len(prompt)] = prompt
    seq[len(prompt):length] = out[:-1]
    ref = family.lm_logits(params, jnp.asarray(seq), hp)
    gaps = np.asarray(token_gaps(ref, jnp.asarray(rows), jnp.asarray(out)))
    if not quant_control:
        return gaps, None
    ctl = family.lm_logits(params, jnp.asarray(seq), hp, quant=True)
    return gaps, np.asarray(control_gaps(ref, ctl, jnp.asarray(rows)))


# --------------------------------------------------------------------------
# retrieval + linearization
# --------------------------------------------------------------------------
PAD, BOS, CTX, SEP, GEN = 0, 1, 2, 3, 4
N_SPECIAL = 6


class RetrievalReference:
    """Host reference of the retrieval path for one corpus and config."""

    def __init__(self, corpus, texts: list, retrieval: dict, serving: dict,
                 tie_tol: float):
        f = corpus.feat.astype(np.float64)
        # both the index and the filter score cosine with a 1e-6 norm guard
        self.unit = f / (np.linalg.norm(f, axis=1, keepdims=True) + 1e-6)
        n = corpus.num_nodes
        src = np.concatenate([corpus.src, corpus.dst])
        dst = np.concatenate([corpus.dst, corpus.src])
        order = np.argsort(src, kind="stable")
        self.adj = dst[order]
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=self.indptr[1:])
        self.n = n
        self.texts = texts
        self.k = int(retrieval["k_seeds"])
        self.hops = int(retrieval["max_hops"])
        self.max_nodes = int(retrieval["max_nodes"])
        self.budget = int(retrieval["filter_budget"])
        self.cap = max(int(retrieval["workset_cap"]), self.max_nodes, self.k)
        self.prompt_cap = int(serving["prompt_cap"])
        self.node_budget = int(serving["node_budget"])
        self.tol = float(tie_tol)
        counts = Counter()
        for t in texts:
            counts.update(t.lower().split())
        self.word_id = {w: N_SPECIAL + i
                        for i, (w, _) in enumerate(counts.most_common(8192))}

    # -- stages -------------------------------------------------------------
    def scores(self, q) -> np.ndarray:
        q = np.asarray(q, np.float64)
        return self.unit @ (q / (np.linalg.norm(q) + 1e-6))

    def neighbors(self, ids: np.ndarray) -> np.ndarray:
        starts, ends = self.indptr[ids], self.indptr[ids + 1]
        lens = ends - starts
        if lens.sum() == 0:
            return np.zeros(0, np.int64)
        offs = np.repeat(starts - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                         lens)
        return self.adj[np.arange(lens.sum()) + offs]

    def bfs(self, seeds) -> np.ndarray:
        """Compact BFS: the workset grows hop by hop from every member's
        neighbours, keeping the ``cap`` smallest by (distance, id), then
        the ``max_nodes`` smallest by (distance, id) are the subgraph."""
        ids = np.unique(np.asarray(seeds, np.int64))[:self.cap]
        dist = np.zeros(len(ids), np.int64)
        for h in range(1, self.hops + 1):
            fresh = np.setdiff1d(np.unique(self.neighbors(ids)), ids)
            fresh = fresh[:self.cap - len(ids)]
            ids = np.concatenate([ids, fresh])
            dist = np.concatenate([dist, np.full(len(fresh), h)])
        order = np.lexsort((ids, dist))[:self.max_nodes]
        return ids[order]

    def linearize(self, query_text: str, node_ids) -> np.ndarray:
        def enc(t):
            return [self.word_id[w] for w in t.lower().split()[:self.node_budget]]

        ids = [BOS] + enc(query_text) + [CTX]
        for v in node_ids:
            nt = enc(self.texts[int(v)])
            if len(ids) + len(nt) + 2 > self.prompt_cap:
                break
            ids += nt + [SEP]
        return np.asarray((ids + [GEN])[:self.prompt_cap], np.int32)

    # -- the comparison ----------------------------------------------------
    def faults(self, query, query_text: str, nodes, prompt) -> list:
        """What the served request got wrong, as readable strings (empty
        when it matches the reference up to near-ties of ``tie_tol``)."""
        out = []
        nodes = np.asarray(nodes, np.int64)
        s = self.scores(query)
        kth = np.sort(s)[-self.k]
        seeds = nodes[:self.k]
        if len(seeds) < self.k or len(set(seeds.tolist())) < self.k:
            return [f"fewer than {self.k} distinct seeds"]
        short = kth - s[seeds]
        if short.max() > self.tol:
            out.append(f"seed {int(seeds[short.argmax()])} scores "
                       f"{short.max():.3g} below the exact {self.k}-th")
        cand = self.bfs(seeds)
        want = min(self.budget, len(cand))
        if len(nodes) != want or len(set(nodes.tolist())) != len(nodes):
            out.append(f"{len(nodes)} filtered nodes, {want} expected")
        if not np.isin(nodes, cand).all():
            out.append("filtered nodes outside the BFS subgraph")
        rest = s[nodes[self.k:]]
        if len(rest) > 1 and (np.diff(rest) > self.tol).any():
            out.append("filtered nodes out of score order")
        left = np.setdiff1d(cand, nodes)
        if len(rest) and len(left) and s[left].max() > rest.min() + self.tol:
            out.append("a better-scoring node was filtered out")
        ref_prompt = self.linearize(query_text, nodes)
        if not np.array_equal(ref_prompt, np.asarray(prompt, np.int32)):
            out.append("prompt differs from the reference linearization")
        return out
