"""Batched serving engine: continuous batching over fixed decode slots.

vLLM-style scheduling adapted to TPU constraints (static shapes): a fixed
(B, cache_len) KV arena; each of the B slots holds one in-flight request.
Every engine step runs ONE jitted dispatch for all slots.  Admission is
batched too: all free slots are refilled together by a single masked batched
prefill — prompts are padded to a shared length bucket, run through one
``tm.prefill`` call, and the resulting cache rows are merged into the arena
with one jitted masked update (never reshaping, never per-slot dispatch).

Length bucketing keeps recompilation bounded: the prefill trace is specialized
on (slots, bucket) only, so at most O(log cache_len) prefill programs exist
over the lifetime of the engine.

Two decode modes share the arena:

* **one-token** (default) — each step is one ``tm.serve_step``: one jitted
  dispatch per output token.  On the contiguous arena, while every slot is
  live and none is known to retire, the next step is dispatched before this
  one's tokens are read (one step of lookahead), so the host's work between
  two steps runs while the chip decodes.
* **self-speculative** (``spec_decode=True``) —
  each step drafts a window of ``draft_window`` tokens per slot from the
  request's own prompt+output history (:mod:`repro.serving.drafter`, no
  second model) and verifies all of them in ONE jitted ``tm.verify_step``
  dispatch.  Greedy argmax verification accepts the longest draft prefix
  that matches what one-token decode would have emitted, so outputs are
  bitwise identical to the one-token schedule while each dispatch can
  commit up to ``draft_window`` tokens (see ``tests/test_spec_decode.py``).

This engine serves already-tokenized prompts.  For the fused
retrieval-to-generation front-end (the RGL "unified system" claim), see
:class:`repro.serving.rag_engine.RAGServeEngine`, which batches graph
retrieval across admissions and feeds this engine.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.models.transformer import model as tm
from repro.models.transformer.config import TransformerConfig
from repro.models.transformer.moe import token_chunks
from repro.serving.config import env_flag
from repro.serving.drafter import draft_tokens


def _auto_block_size(cache_len: int, preferred: int = 16) -> int:
    """Largest block size <= ``preferred`` dividing ``cache_len``, so the
    paged arena works for any arena length without per-caller block-size
    plumbing."""
    for b in range(min(preferred, cache_len), 0, -1):
        if cache_len % b == 0:
            return b
    return 1


@dataclasses.dataclass
class Request:
    uid: int
    prompt_ids: np.ndarray  # (L,) int32
    max_new_tokens: int = 32
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # retired early by KV exhaustion (arena full, or paged pool empty):
    # out_tokens is shorter than max_new_tokens and did not end at EOS
    truncated: bool = False
    # retired by ServeEngine.abort(): whatever tokens were emitted so far
    # are kept, ``error`` carries the abort reason
    failed: bool = False
    error: Optional[str] = None
    # monotonic admission ticket assigned by the submitting front-end; a
    # stable identity that, unlike id(self), is never reused after GC
    ticket: int = -1
    # prefix sharing (paged arena + prefix_share, set by the RAG layer):
    # ``shared_prefix`` names a CachedRetrieval whose pinned prefilled KV
    # blocks cover this request's exact prompt — admission re-validates and
    # aliases them instead of running prefill; ``pin_to`` names an entry
    # that should receive this request's freshly prefilled prompt blocks as
    # its pin (the donor side).  Both are best-effort: a released pin or a
    # prompt mismatch falls back to the ordinary prefill path.
    shared_prefix: object = None
    pin_to: object = None
    # the engine's clock (``now_fn``) when the first token reached the host
    first_token_at: Optional[float] = None


@dataclasses.dataclass
class _SharePlan:
    """Admission-time snapshot of a validated prefix share.  Snapshotting
    (plus the refcount holds the engine takes when the plan is made)
    decouples the admission dispatch from the donor entry: a cache eviction
    or pin reclaim between planning and dispatch cannot invalidate the
    blocks mid-wave."""

    blocks: np.ndarray  # all ceil(L/bs) donor prompt blocks, table order
    nfull: int  # full leading blocks to alias
    tail: int  # donor's partial tail block to COW-copy, -1 if none
    length: int  # prompt tokens covered
    first_tok: int  # the donor prefill's recorded argmax


@dataclasses.dataclass
class _Dispatched:
    """A one-token step on the chip whose tokens the host has not read."""

    nxt: jax.Array  # (slots,) the step's tokens
    served: tuple  # the request each slot decoded for (None if dead)
    cursor: np.ndarray  # every slot's cursor after the step


def _bucket_len(n: int, cache_len: int, floor: int = 8) -> int:
    """Smallest power-of-two >= n (>= floor), capped at cache_len."""
    b = floor
    while b < n:
        b <<= 1
    return min(b, cache_len)


@functools.partial(jax.jit, static_argnames=("cfg", "cache_len"))
def _prefill_batch(params, toks, tl, cfg: TransformerConfig, cache_len: int):
    """Module-level jit so traces are shared across engine instances —
    constructing a fresh engine must not recompile the serving programs."""
    return tm.prefill(params, toks, tl, cfg, cache_len)


@functools.partial(jax.jit, static_argnames=("cfg", "n_draft", "eos_id"))
def _spec_step(params, cache, cur_tok, hist, hist_len, max_new, out_len,
               cfg: TransformerConfig, n_draft: int, eos_id):
    """ONE fused dispatch per speculative engine step: prompt-lookup draft,
    per-slot acceptance room, windowed verify, acceptance + cursor rewind,
    and the history append of the accepted tokens.  Keeping the drafter,
    the room computation, and the history update inside the same jit
    matters on dispatch-bound hosts: at small model sizes each extra jitted
    call or host->device transfer costs about as much as the verify compute
    itself, so the host only downloads (greedy, accepted) per step and only
    uploads state at admission waves.

    max_new / out_len (B,) int32 are device mirrors of each slot's token
    budget and emitted count (pinned at admission, advanced here), so
    ``room = min(max_new - out_len, cache_len - cursor)`` — the clamp that
    keeps a window from overshooting ``max_new_tokens`` or the arena —
    never syncs the host.
    """
    drafts = draft_tokens(hist, hist_len, n_draft)
    fed = jnp.concatenate([cur_tok[:, None], drafts], axis=1)
    sc = cache.k.shape[2]
    room = jnp.minimum(max_new - out_len, sc - cache.cursor).astype(jnp.int32)
    greedy, accepted, nxt, cache = tm.verify_step(
        params, cache, fed, room, cfg, eos_id=eos_id
    )
    # append the accepted tokens to each slot's history (device-resident:
    # the host never re-uploads the arena between admissions)
    h = hist.shape[1]
    cols = jnp.arange(h, dtype=jnp.int32)[None, :]
    for i in range(n_draft + 1):
        write = (i < accepted)[:, None] & (cols == (hist_len + i)[:, None])
        hist = jnp.where(write, greedy[:, i:i + 1], hist)
    hist_len = jnp.minimum(hist_len + accepted, h)
    # pack (greedy, accepted) into ONE host-bound buffer: the engine's per-
    # step sync is a single device->host transfer, like one-token decode's
    packed = jnp.concatenate([greedy, accepted[:, None]], axis=1)
    return packed, nxt, cache, hist, hist_len, out_len + accepted


@jax.jit
def _merge_admitted(arena: tm.KVCache, new: tm.KVCache, cur_tok, first,
                    rows, newly):
    """Masked merge of freshly prefilled rows into the slot arena.

    ``rows[i]`` names the prefill-batch row feeding slot i; ``newly[i]`` masks
    which slots actually admit.  Elementwise select => shards cleanly.
    """

    def mix_b1(a, b):  # (L, B, ...) — batch on axis 1 (k/v/scales)
        if a is None:
            return None
        m = newly.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, b[:, rows], a)

    def mix_b0(a, b):  # (B, ...) — batch on axis 0 (pos/cursor)
        if a is None:
            return None
        m = newly.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, b[rows], a)

    cache = tm.KVCache(
        k=mix_b1(arena.k, new.k),
        v=mix_b1(arena.v, new.v),
        pos=mix_b0(arena.pos, new.pos),
        cursor=mix_b0(arena.cursor, new.cursor),
        k_scale=mix_b1(arena.k_scale, new.k_scale),
        v_scale=mix_b1(arena.v_scale, new.v_scale),
        routed=arena.routed,
        max_load=arena.max_load,
    )
    return cache, jnp.where(newly, first[rows], cur_tok)


@functools.partial(jax.jit, static_argnames=("block_size",))
def _paged_merge_admitted(arena: "tm.PagedKVCache", new: tm.KVCache, cur_tok,
                          first, rows, newly, tl, block_size: int):
    """Paged-arena admission merge: allocate each admitted slot's prompt
    blocks (ceil(L/bs)) from the free stack and scatter its freshly
    prefilled rows into the pool.  ``tl`` (B,) is the per-SLOT prompt
    length (0 where not admitting); pos/cursor/cur_tok merge with the same
    semantics as :func:`_merge_admitted`."""
    bs = block_size
    b, sc = arena.pos.shape
    p_rows = arena.k.shape[1]
    m = arena.table.shape[1]
    target = jnp.where(newly, (tl + bs - 1) // bs, 0)
    table, n_free, ref = tm.alloc_blocks(
        arena.table, arena.free, arena.n_free, arena.ref, target, newly, m
    )
    rowmap = tm.block_rows(table, bs)  # (B, Sc)
    spos = jnp.arange(sc, dtype=jnp.int32)[None, :]
    # scatter every row of the allocated blocks (zero-padding past the
    # prompt included — pos == -1 masks it, same as the contiguous merge);
    # rows past the allocation go out of range and drop
    valid = newly[:, None] & (spos < target[:, None] * bs)
    dst = jnp.where(valid, rowmap, p_rows).reshape(-1)  # (B*Sc,)

    def scat(pool, fresh):  # fresh (L, B, Sc, ...) -> pool (L, P, ...)
        if pool is None:
            return None
        vals = fresh[:, rows].reshape(
            (fresh.shape[0], b * sc) + fresh.shape[3:]
        )
        return pool.at[:, dst].set(vals, mode="drop")

    pos_new = jnp.where(spos < tl[:, None], spos, -1)
    cache = tm.PagedKVCache(
        k=scat(arena.k, new.k),
        v=scat(arena.v, new.v),
        pos=jnp.where(newly[:, None], pos_new, arena.pos),
        cursor=jnp.where(newly, tl.astype(jnp.int32), arena.cursor),
        table=table,
        free=arena.free,
        n_free=n_free,
        ref=ref,
        k_scale=scat(arena.k_scale, new.k_scale),
        v_scale=scat(arena.v_scale, new.v_scale),
    )
    return cache, jnp.where(newly, first[rows], cur_tok)


@functools.partial(
    jax.jit, static_argnames=("cfg", "n_draft", "eos_id", "block_size")
)
def _paged_spec_step(params, cache, cur_tok, hist, hist_len, max_new,
                     out_len, live, cfg: TransformerConfig, n_draft: int,
                     eos_id, block_size: int):
    """:func:`_spec_step` over the paged pool: identical draft / room /
    acceptance / history arithmetic (so outputs stay bitwise identical to
    the contiguous arena), with ``live`` gating the pool allocator and the
    block scatters inside :func:`tm.paged_verify_step`."""
    drafts = draft_tokens(hist, hist_len, n_draft)
    fed = jnp.concatenate([cur_tok[:, None], drafts], axis=1)
    sc = cache.pos.shape[1]
    room = jnp.minimum(max_new - out_len, sc - cache.cursor).astype(jnp.int32)
    greedy, accepted, nxt, cache = tm.paged_verify_step(
        params, cache, fed, room, live, cfg, eos_id=eos_id,
        block_size=block_size,
    )
    h = hist.shape[1]
    cols = jnp.arange(h, dtype=jnp.int32)[None, :]
    for i in range(n_draft + 1):
        write = (i < accepted)[:, None] & (cols == (hist_len + i)[:, None])
        hist = jnp.where(write, greedy[:, i:i + 1], hist)
    hist_len = jnp.minimum(hist_len + accepted, h)
    packed = jnp.concatenate([greedy, accepted[:, None]], axis=1)
    return packed, nxt, cache, hist, hist_len, out_len + accepted


class ServeEngine:
    """Continuous-batching decode server over a fixed KV arena.

    Usage::

        eng = ServeEngine(params, cfg, slots=8, cache_len=512)
        eng.submit(Request(uid=0, prompt_ids=ids, max_new_tokens=32))
        finished = eng.run_to_completion()

    Every option is a concrete value (default: one-token decode on the
    contiguous arena); the engine reads no option from the environment.
    ``RGL_*`` variables reach it only through a resolved
    :class:`~repro.serving.config.ServingConfig` (``RAGServeEngine``,
    ``repro.launch.serve``).

    ``spec_decode=True`` verifies ``draft_window`` fed tokens per step.
    ``paged_kv=True`` makes the KV arena a shared pool of
    ``kv_pool_blocks`` blocks of ``kv_block_size`` tokens
    (:class:`repro.models.transformer.model.PagedKVCache`): a slot only
    holds blocks its cursor has actually crossed, and returns them the
    step its request retires, so total KV memory tracks *live tokens*
    instead of ``slots * cache_len``.  Outputs are bitwise identical to
    the contiguous arena in both decode modes.  ``kv_block_size=None``
    picks the largest divisor of ``cache_len`` <= 16;
    ``kv_pool_blocks=None`` sizes the pool to full
    capacity (``slots * cache_len / kv_block_size`` — never truncates).  An
    undersized pool is the memory-saving mode: admission gates on block
    availability (FIFO — an oversized head-of-line request blocks the
    queue rather than being skipped), and when live slots outgrow the
    pool mid-decode the engine retires the highest-indexed needy slot
    with ``truncated=True`` *before* the dispatch, so the in-jit
    allocator never over-pops and never needs a host sync.
    """

    def __init__(
        self, params, cfg: TransformerConfig, *, slots: int = 8,
        cache_len: int = 512, eos_id: Optional[int] = None,
        spec_decode: bool = False, draft_window: int = 4,
        paged_kv: bool = False, kv_block_size: Optional[int] = None,
        kv_pool_blocks: Optional[int] = None, prefix_share: bool = False,
        now_fn=time.monotonic,
    ):
        self.params = params
        self._now = now_fn
        self.cfg = cfg
        self.slots = slots
        self.cache_len = cache_len
        self.eos_id = eos_id
        self.spec_decode = bool(spec_decode)
        self.draft_window = int(draft_window)
        if self.spec_decode and self.draft_window < 2:
            raise ValueError(
                f"draft_window must be >= 2 (1 committed token + >= 1 draft),"
                f" got {self.draft_window}"
            )
        self.queue: deque = deque()
        self.active: list = [None] * slots
        self.live = np.zeros(slots, bool)
        self.paged_kv = bool(paged_kv)
        if cfg.mla is not None and (self.paged_kv or self.spec_decode):
            raise ValueError(
                f"config {cfg.name!r} uses latent attention (MLA), whose "
                f"latent cache lives on the contiguous arena with one-token "
                f"decode only; the paged arena and speculative decoding do "
                f"not carry it (paged_kv={self.paged_kv}, "
                f"spec_decode={self.spec_decode})")
        # prefix sharing is a paged-arena feature: on a contiguous arena the
        # flag is inert (admission behaves exactly as before), so the
        # contiguous cells of the CI matrix double as the fallback parity leg
        self.prefix_share = bool(prefix_share) and self.paged_kv
        self.truncations = 0  # requests retired by KV exhaustion (both modes)
        if self.paged_kv:
            bs = _auto_block_size(cache_len) if kv_block_size is None \
                else int(kv_block_size)
            if bs < 1 or cache_len % bs != 0:
                raise ValueError(
                    f"kv_block_size={bs} must divide cache_len={cache_len}"
                )
            self.block_size = bs
            self.max_blocks = cache_len // bs
            self.pool_blocks = slots * self.max_blocks \
                if kv_pool_blocks is None else int(kv_pool_blocks)
            if self.pool_blocks < self.max_blocks:
                raise ValueError(
                    f"kv_pool_blocks={self.pool_blocks} cannot hold even one "
                    f"full-length request ({self.max_blocks} blocks)"
                )
            self.cache = tm.init_paged_cache(
                cfg, slots, cache_len, bs, self.pool_blocks
            )
            # host mirrors of the device allocator state: admission and
            # every dispatch replay the same block arithmetic the jitted
            # allocator runs, so exhaustion checks never sync the device.
            # The mirror is now content-exact, not just depth-exact — the
            # stack's block ids and per-block refcounts are replayed so the
            # host always knows WHICH blocks a slot holds (the retrieval
            # cache pins concrete block ids, and refcounted frees return a
            # data-dependent subset of a retiring slot's blocks)
            self._free_stack: list = list(range(self.pool_blocks))
            self._ref_host = np.zeros(self.pool_blocks, np.int32)
            self._slot_blocks: list = [[] for _ in range(slots)]
            self.pool_high_water = 0  # max blocks ever simultaneously held
            self._live_dev = jnp.asarray(self.live)
            self._live_dirty = False
        else:
            self.cache = tm.init_cache(cfg, slots, cache_len)
        # pre-dispatch invariant guard (satellite of the alloc_blocks
        # sum(need) <= n_free contract): raises host-side with slot/pool
        # counters instead of letting the jitted allocator silently alias
        # stale stack entries.  Env-gated; tests/conftest.py turns it on.
        self._kv_debug = env_flag("RGL_KV_DEBUG")
        # prefix-sharing hooks + telemetry.  kv_pin_gate: entry -> bool,
        # consulted before pinning prompt blocks to a retrieval-cache entry
        # (the RAG layer wires a residency check so blocks are never pinned
        # to an entry that was already evicted).  kv_pin_reclaim:
        # want_blocks -> freed, consulted under pool pressure so cache pins
        # are released before any live request is truncated.
        self.kv_pin_gate = None
        self.kv_pin_reclaim = None
        self.kv_pins = 0  # entries that received a prompt-block pin
        self.kv_releases = 0  # pins released (eviction / reclaim)
        self.kv_pinned_blocks = 0  # blocks currently held by pins
        self.kv_shared_admits = 0  # admissions served by aliased blocks
        self.kv_reused_tokens = 0  # prompt tokens whose prefill was skipped
        self.kv_cow_copies = 0  # partial tail blocks copied at adoption
        self.prefill_batches = 0  # prefill dispatches issued by _admit
        self.prefill_rows = 0  # prompts actually prefilled
        self.admit_seconds = 0.0  # wall time inside _admit
        self.cur_tok = jnp.zeros((slots,), jnp.int32)
        # per-slot token history arena for the prompt-lookup drafter:
        # prompt + every emitted token, left-aligned.  hist_cap bounds the
        # total (prompt < cache_len, decode stops at cursor == cache_len).
        # The host mirror is written at admission and uploaded once per
        # admission wave; between admissions the device copy evolves inside
        # _spec_step and the mirror tracks it via _hist_append.
        self._hist_cap = cache_len + 1
        self.hist = np.zeros((slots, self._hist_cap), np.int32)
        self.hist_len = np.zeros((slots,), np.int32)
        self._hist_dev = jnp.asarray(self.hist)
        self._hist_len_dev = jnp.asarray(self.hist_len)
        # host-tracked cursor mirror: admission pins it to the prompt length,
        # every decode dispatch advances it by the committed token count, so
        # finish checks and speculative room never sync on the device cursor
        self._cursor = np.zeros((slots,), np.int64)
        # device mirrors of each slot's token budget / emitted count for the
        # in-jit acceptance-room clamp (uploaded only at admission waves)
        self._max_new = np.ones((slots,), np.int32)
        self._out_len = np.zeros((slots,), np.int32)
        self._max_new_dev = jnp.asarray(self._max_new)
        self._out_len_dev = jnp.asarray(self._out_len)
        # decode telemetry (both modes): dispatches vs tokens committed
        self.decode_steps = 0  # jitted decode/verify dispatches
        self.slot_steps = 0  # live-slot decode opportunities (slots x steps)
        self.emitted_tokens = 0  # all tokens committed (incl. prefill firsts)
        self.decode_tokens = 0  # tokens committed by decode dispatches
        self.draft_proposed = 0  # draft tokens fed to verification
        self.draft_accepted = 0  # drafts accepted (excludes the free token)
        # one-token lookahead: the step already on the chip, read next call
        self._ahead: Optional[_Dispatched] = None
        self.ahead_steps = 0  # steps read that were dispatched ahead

    @property
    def free_slots(self) -> int:
        """Decode slots that remain free once the admission queue drains —
        the backpressure signal for async retrieval prefetch (collect a
        prefetched wave only when it can actually be admitted)."""
        return max(0, int(self.slots - self.live.sum()) - len(self.queue))

    # -- paged-pool host bookkeeping ------------------------------------------
    @property
    def _free_host(self) -> int:
        """Free-stack depth (host mirror) — kept as the historical name so
        existing telemetry and tests read it unchanged."""
        return len(self._free_stack)

    @property
    def _ntab(self) -> np.ndarray:
        """Per-slot allocated-block counts, derived from the content-exact
        block-id mirror (historical name, see ``_slot_blocks``)."""
        return np.array([len(b) for b in self._slot_blocks], np.int64)

    def _blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)  # ceil division

    def _live_mask(self):
        """Device live mask for the paged dispatches, re-uploaded only when
        liveness changed (H2D upload, never a D2H sync)."""
        if self._live_dirty:
            self._live_dev = jnp.asarray(self.live)
            self._live_dirty = False
        return self._live_dev

    def _guard_alloc(self, need_total: int, where: str) -> None:
        """RGL_KV_DEBUG tripwire for the ``sum(need) <= n_free`` contract of
        ``tm.alloc_blocks``: a violation on device silently aliases stale
        free-stack entries (two slots end up writing the same pool block);
        here it raises with the counters needed to debug the accounting."""
        if self._kv_debug and need_total > len(self._free_stack):
            raise RuntimeError(
                f"paged-KV alloc invariant violated at {where}: dispatch "
                f"would pop {need_total} blocks but the free stack holds "
                f"{len(self._free_stack)} (pool_blocks={self.pool_blocks}, "
                f"pinned={self.kv_pinned_blocks}, "
                f"live={int(self.live.sum())}, "
                f"per-slot blocks={[len(b) for b in self._slot_blocks]})"
            )

    def _pop_host(self, slot: int, n: int) -> list:
        """Replay ``n`` free-stack pops for ``slot`` on the host mirrors —
        exactly the device allocator's order (sequential from the top)."""
        out = []
        for _ in range(n):
            blk = self._free_stack.pop()
            self._ref_host[blk] = 1
            self._slot_blocks[slot].append(blk)
            out.append(blk)
        return out

    def _host_release(self, drops: dict) -> int:
        """Replay refcount drops on the host mirrors: decrement each block's
        count, push blocks hitting zero back in ascending-id order (the
        device's cumsum-compaction order).  Returns blocks pushed."""
        pushed = []
        for blk in sorted(drops):
            r = int(self._ref_host[blk]) - drops[blk]
            if r < 0 and self._kv_debug:
                raise RuntimeError(
                    f"double-free of pool block {blk}: dropping "
                    f"{drops[blk]} holds but refcount is "
                    f"{int(self._ref_host[blk])} (pool_blocks="
                    f"{self.pool_blocks}, pinned={self.kv_pinned_blocks})"
                )
            self._ref_host[blk] = max(r, 0)
            if drops[blk] > 0 and r <= 0:
                pushed.append(blk)
        self._free_stack.extend(pushed)
        return len(pushed)

    def _free_slots_paged(self, slot_ids) -> None:
        """Drop the named slots' holds on their blocks: one jitted dispatch,
        mirrored on host.  Blocks shared with other slots or pinned by the
        retrieval cache stay out of the free stack until their last holder
        lets go."""
        mask = np.zeros(self.slots, bool)
        mask[list(slot_ids)] = True
        self.cache = tm.free_slot_blocks(self.cache, jnp.asarray(mask))
        drops: dict = {}
        for i in slot_ids:
            for blk in self._slot_blocks[i]:
                drops[blk] = drops.get(blk, 0) + 1
            self._slot_blocks[i] = []
        self._host_release(drops)
        self._live_dirty = True

    def _release_retired(self, live_before: np.ndarray) -> None:
        """Free the blocks of every slot that retired during this step's
        finish checks (batched into one dispatch)."""
        retired = np.where(live_before & ~self.live)[0]
        if retired.size:
            self._free_slots_paged(retired.tolist())

    def _paged_step_need(self) -> np.ndarray:
        """Per-slot blocks the next dispatch's in-jit allocator will pop —
        the identical arithmetic replayed on the host mirrors (cursor and
        table-prefix counts advance deterministically, so the two never
        diverge)."""
        w = self.draft_window if self.spec_decode else 1
        need = np.zeros(self.slots, np.int64)
        for i in range(self.slots):
            if not self.live[i]:
                continue
            hi = min(int(self._cursor[i]) + w, self.cache_len)
            need[i] = max(self._blocks_for(hi) - len(self._slot_blocks[i]), 0)
        return need

    def _reclaim_pins(self, deficit: int) -> int:
        """Ask the cache tier (via the RAG layer's hook) to release pinned
        prefilled-KV blocks under pool pressure — cache pins must never cost
        a live request tokens, so this runs before any truncation."""
        if self.kv_pin_reclaim is None or deficit <= 0:
            return 0
        return int(self.kv_pin_reclaim(int(deficit)))

    def _retire_pool_exhausted(self) -> list:
        """Host-side pre-dispatch exhaustion check: while the pool cannot
        cover every live slot's next-step allocation, first release cache
        pins, then retire the highest-indexed slot that needs a block
        (``truncated=True``) and reclaim its blocks.  Deterministic, and it
        guarantees the jitted allocator never over-pops — the device needs
        no exhaustion path."""
        finished = []
        need = self._paged_step_need()
        self._reclaim_pins(int(need.sum()) - self._free_host)
        while need.sum() > self._free_host:
            needy = np.where(need > 0)[0]
            i = int(needy[-1])
            req = self.active[i]
            req.done = True
            req.truncated = True
            self.truncations += 1
            finished.append(req)
            self.active[i] = None
            self.live[i] = False
            self._free_slots_paged([i])
            need[i] = 0
        return finished

    def _apply_paged_alloc(self) -> None:
        """Advance the host allocator mirrors by exactly what the dispatch
        being issued will pop on device."""
        need = self._paged_step_need()
        tot = int(need.sum())
        if tot:
            self._guard_alloc(tot, "decode step")
            for i in range(self.slots):
                if need[i]:
                    self._pop_host(i, int(need[i]))
        self.pool_high_water = max(
            self.pool_high_water, self.pool_blocks - self._free_host
        )

    # -- prefix sharing: pins, plans, adoption --------------------------------
    def _acquire_host(self, ids) -> None:
        self.cache = tm.acquire_blocks(
            self.cache, jnp.asarray(np.asarray(ids, np.int32))
        )
        for blk in ids:
            self._ref_host[int(blk)] += 1

    def _release_ids(self, ids) -> int:
        """Drop one hold per listed block (device + host mirrors); returns
        how many blocks actually returned to the free stack."""
        self.cache = tm.release_blocks(
            self.cache, jnp.asarray(np.asarray(ids, np.int32))
        )
        drops: dict = {}
        for blk in ids:
            drops[int(blk)] = drops.get(int(blk), 0) + 1
        return self._host_release(drops)

    def _pin_entry(self, entry, slot: int, req: "Request", tok0: int) -> None:
        """Attach the freshly prefilled prompt blocks of ``slot`` to the
        retrieval-cache entry that produced the prompt: the pin takes one
        refcount hold per block, records the exact prompt and first token,
        and registers a release hook the cache calls on eviction."""
        if getattr(entry, "kv_blocks", None) is not None:
            return  # already pinned (by this request's wave-mate or earlier)
        if self.kv_pin_gate is not None and not self.kv_pin_gate(entry):
            return  # entry no longer resident — pinning would leak blocks
        L = len(req.prompt_ids)
        blocks = np.asarray(
            self._slot_blocks[slot][:self._blocks_for(L)], np.int32
        )
        if blocks.size == 0:
            return
        self._acquire_host(blocks)
        entry.kv_blocks = blocks
        entry.kv_len = L
        entry.kv_first_tok = int(tok0)
        entry.kv_prompt = np.asarray(req.prompt_ids, np.int32).copy()
        entry.kv_owner = self
        entry.kv_release = self._release_kv_pin
        self.kv_pins += 1
        self.kv_pinned_blocks += int(blocks.size)

    def _release_kv_pin(self, entry) -> int:
        """Release an entry's prompt-block pin (cache eviction hook and the
        pool-pressure reclaim path).  Idempotent; returns how many blocks
        actually came back to the free stack (blocks still aliased by live
        slots stay out until those slots retire)."""
        blocks = getattr(entry, "kv_blocks", None)
        if blocks is None:
            return 0
        entry.kv_blocks = None
        entry.kv_prompt = None
        entry.kv_owner = None
        entry.kv_release = None
        self.kv_releases += 1
        self.kv_pinned_blocks -= int(np.asarray(blocks).size)
        return self._release_ids(list(np.asarray(blocks)))

    def _plan_share(self, req: "Request"):
        """Validate a request's ``shared_prefix`` against the entry's pin at
        admission time and snapshot it into a :class:`_SharePlan`, taking
        one refcount hold per donor block so nothing the plan references
        can be recycled before the adoption dispatch.  Returns None (and
        takes no holds) when the pin is gone, owned by another engine's
        pool, or covers a different prompt — the request then just prefills
        fresh, which is always correct."""
        entry = req.shared_prefix
        if entry is None:
            return None
        blocks = getattr(entry, "kv_blocks", None)
        if blocks is None or getattr(entry, "kv_owner", None) is not self:
            return None
        kp = getattr(entry, "kv_prompt", None)
        pi = np.asarray(req.prompt_ids, np.int32)
        if kp is None or len(kp) != len(pi) or not np.array_equal(kp, pi):
            return None
        L = int(entry.kv_len)
        blocks = np.asarray(blocks, np.int32)
        nfull = L // self.block_size
        tail = int(blocks[-1]) if L % self.block_size else -1
        plan = _SharePlan(blocks=blocks, nfull=nfull, tail=tail, length=L,
                          first_tok=int(entry.kv_first_tok))
        self._acquire_host(blocks)
        return plan

    def _drop_plan(self, plan: "_SharePlan") -> None:
        """Release a plan's holds without admitting it (gate backout)."""
        self._release_ids(list(plan.blocks))

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt_ids) >= self.cache_len:
            raise ValueError(
                f"prompt of {len(req.prompt_ids)} tokens cannot fit "
                f"cache_len={self.cache_len} (need room for >=1 new token)"
            )
        self.queue.append(req)

    def abort(self, reason: str = "aborted") -> list:
        """Retire every queued and live request (``failed=True``, partial
        ``out_tokens`` kept) and reconcile the arena: slots freed, paged KV
        blocks returned to the pool, queue cleared.  The engine is reusable
        afterwards — a fresh workload admits into a clean arena.  Returns
        the aborted requests."""
        out = []
        self._ahead = None  # its tokens belong to the requests aborted here
        live_idx = [i for i in range(self.slots) if self.live[i]]
        for i in live_idx:
            req = self.active[i]
            req.done = True
            req.failed = True
            req.error = reason
            self.active[i] = None
            self.live[i] = False
            out.append(req)
        if self.paged_kv and live_idx:
            self._free_slots_paged(live_idx)
        while self.queue:
            req = self.queue.popleft()
            req.done = True
            req.failed = True
            req.error = reason
            out.append(req)
        return out

    def _admit(self) -> list:
        t0 = time.perf_counter()
        try:
            return self._admit_inner()
        finally:
            self.admit_seconds += time.perf_counter() - t0

    def _admit_inner(self) -> list:
        """Refill free slots with one masked batched prefill.  Returns the
        requests that finish AT admission (first token hits EOS, or
        ``max_new_tokens == 1``) — they never occupy a live slot, so a
        request can never emit more than ``max_new_tokens`` tokens.

        Paged arena: admission additionally gates on free blocks —
        ceil((L+1)/bs) per request, prompt plus the first decode write, so
        an admit is never pool-truncated on its very first step.  FIFO is
        preserved: a head-of-line request that does not fit blocks the
        rest of the queue instead of being skipped (full-size pools never
        gate, keeping admission identical to the contiguous schedule).

        Prefix sharing (``prefix_share``): a request whose validated
        ``shared_prefix`` entry pins this pool's blocks skips the prefill
        batch entirely — its plan aliases the donor's full blocks and
        COW-copies the partial tail in one ``tm.adopt_prefix_blocks``
        dispatch, so it only needs the gate's usual one-extra-block
        reservation.  Under pool pressure the gate releases cache pins
        before refusing a head-of-line request, so sharing never admits
        *less* than the unshared schedule would."""
        free = [i for i in range(self.slots) if not self.live[i]]
        plans: dict = {}  # queue position taken -> _SharePlan
        if self.paged_kv:
            take = 0
            taken = 0  # blocks already committed to earlier takes
            for r in list(self.queue)[:len(free)]:
                full_need = self._blocks_for(
                    min(len(r.prompt_ids) + 1, self.cache_len)
                )
                plan = self._plan_share(r) if self.prefix_share else None
                need = full_need - plan.nfull if plan is not None \
                    else full_need
                if need > self._free_host - taken:
                    self._reclaim_pins(need - (self._free_host - taken))
                if need > self._free_host - taken:
                    if plan is not None:
                        self._drop_plan(plan)
                    break
                if plan is not None:
                    plans[take] = plan
                taken += need
                take += 1
        else:
            take = min(len(free), len(self.queue))
        if take == 0:
            return []
        reqs = [self.queue.popleft() for _ in range(take)]
        slot_ids = free[:take]
        first_by_slot = np.zeros(self.slots, np.int64)
        # -- fresh population: one masked batched prefill (batch padded to
        # `slots` rows, lengths padded to a shared power-of-two bucket)
        fresh_pairs = [(j, i) for j, i in enumerate(slot_ids)
                       if j not in plans]
        if fresh_pairs:
            bucket = _bucket_len(
                max(len(reqs[j].prompt_ids) for j, _ in fresh_pairs),
                self.cache_len,
            )
            args = {"rows": len(fresh_pairs), "bucket": bucket}
            moe = self.cfg.moe
            if moe is not None and moe.capacity_factor is None:
                # the held experts' token chunks in this prefill (0 = one
                # dense pass; moe.token_chunks)
                args["expert_chunks"] = token_chunks(self.slots * bucket)
            with tracing.span("prefill", **args):
                self._prefill(reqs, fresh_pairs, bucket, first_by_slot)
        # -- shared population: alias donor blocks, no prefill dispatch
        if plans:
            mask = np.zeros(self.slots, bool)
            src_table = np.full((self.slots, self.max_blocks), -1, np.int32)
            length = np.zeros(self.slots, np.int32)
            tail = np.full(self.slots, -1, np.int32)
            firsts = np.zeros(self.slots, np.int32)
            for j, i in enumerate(slot_ids):
                plan = plans.get(j)
                if plan is None:
                    continue
                mask[i] = True
                src_table[i, :plan.nfull] = plan.blocks[:plan.nfull]
                length[i] = plan.length
                tail[i] = plan.tail
                firsts[i] = plan.first_tok
                first_by_slot[i] = plan.first_tok
            self._guard_alloc(int((tail >= 0).sum()), "prefix-share adopt")
            self.cache, self.cur_tok = tm.adopt_prefix_blocks(
                self.cache, self.cur_tok, jnp.asarray(mask),
                jnp.asarray(src_table), jnp.asarray(length),
                jnp.asarray(tail), jnp.asarray(firsts), self.block_size,
            )
            # host replay, in the dispatch's order: tail pops (slot index
            # ascending), then the one-dispatch tail-source holds release
            tail_drops: dict = {}
            for j, i in enumerate(slot_ids):
                plan = plans.get(j)
                if plan is None:
                    continue
                self._slot_blocks[i] = [int(b)
                                        for b in plan.blocks[:plan.nfull]]
                if plan.tail >= 0:
                    self._pop_host(i, 1)
                    tail_drops[plan.tail] = tail_drops.get(plan.tail, 0) + 1
                    self.kv_cow_copies += 1
                self.kv_shared_admits += 1
                self.kv_reused_tokens += plan.length
            self._host_release(tail_drops)
            self._live_dirty = True
        if self.paged_kv:
            self.pool_high_water = max(
                self.pool_high_water, self.pool_blocks - self._free_host
            )
        finished = []
        dead_at_admission = []
        now = self._now()
        for j, i in enumerate(slot_ids):
            req = reqs[j]
            tok0 = int(first_by_slot[i])
            req.out_tokens.append(tok0)
            req.first_token_at = now
            self.emitted_tokens += 1
            L = len(req.prompt_ids)
            self._cursor[i] = L  # merge/adopt pinned this slot's cursor
            if (self.prefix_share and j not in plans
                    and req.pin_to is not None):
                # donor side: hand this prompt's freshly prefilled blocks to
                # the retrieval-cache entry so the next identical prompt
                # skips prefill
                self._pin_entry(req.pin_to, i, req, tok0)
            hit_eos = self.eos_id is not None and tok0 == self.eos_id
            if hit_eos or len(req.out_tokens) >= req.max_new_tokens:
                # done at admission: the arena row was written but the slot
                # never goes live, so the next wave simply reuses it
                req.done = True
                finished.append(req)
                dead_at_admission.append(i)
                continue
            self.active[i] = req
            self.live[i] = True
            self.hist[i, :L] = np.asarray(req.prompt_ids, np.int32)
            self.hist[i, L] = tok0
            self.hist_len[i] = L + 1
            self._max_new[i] = req.max_new_tokens
            self._out_len[i] = 1
        if self.paged_kv and dead_at_admission:
            # admission allocated these slots' prompt blocks, but the slot
            # never went live — give the blocks straight back (pinned or
            # still-shared blocks stay with their remaining holders)
            self._free_slots_paged(dead_at_admission)
        if self.spec_decode:
            self._hist_dev = jnp.asarray(self.hist)
            self._hist_len_dev = jnp.asarray(self.hist_len)
            self._max_new_dev = jnp.asarray(self._max_new)
            self._out_len_dev = jnp.asarray(self._out_len)
        return finished

    def _prefill(self, reqs: list, fresh_pairs: list, bucket: int,
                 first_by_slot: np.ndarray) -> None:
        """One masked batched prefill of ``fresh_pairs`` (request index,
        slot) padded to ``bucket``, merged into the arena; each slot's first
        token lands in ``first_by_slot``."""
        toks = np.zeros((self.slots, bucket), np.int32)
        tl = np.zeros((self.slots,), np.int32)
        for f, (j, _) in enumerate(fresh_pairs):
            L = len(reqs[j].prompt_ids)  # submit() guarantees L < Sc
            toks[f, :L] = np.asarray(reqs[j].prompt_ids, np.int32)
            tl[f] = L
        logits, fresh = _prefill_batch(
            self.params, jnp.asarray(toks), jnp.asarray(tl),
            self.cfg, self.cache_len,
        )
        self.prefill_batches += 1
        self.prefill_rows += len(fresh_pairs)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (slots,)
        rows = np.zeros(self.slots, np.int32)
        newly = np.zeros(self.slots, bool)
        tl_slot = np.zeros(self.slots, np.int32)
        for f, (j, i) in enumerate(fresh_pairs):
            rows[i] = f
            newly[i] = True
            tl_slot[i] = tl[f]
        if self.paged_kv:
            self._guard_alloc(
                sum(self._blocks_for(int(t)) for t in tl_slot),
                "admission prefill merge",
            )
            self.cache, self.cur_tok = _paged_merge_admitted(
                self.cache, fresh, self.cur_tok, first,
                jnp.asarray(rows), jnp.asarray(newly),
                jnp.asarray(tl_slot), self.block_size,
            )
            # replay the merge's pops: slot-index ascending, exactly the
            # device allocator's order
            for f, (j, i) in enumerate(fresh_pairs):
                self._pop_host(i, self._blocks_for(int(tl[f])))
            self._live_dirty = True
        else:
            self.cache, self.cur_tok = _merge_admitted(
                self.cache, fresh, self.cur_tok, first,
                jnp.asarray(rows), jnp.asarray(newly),
            )
        with tracing.span("prefill.wait"):
            first_np = np.asarray(first)
        for f, (j, i) in enumerate(fresh_pairs):
            first_by_slot[i] = int(first_np[f])

    def _hist_append(self, i: int, toks: list) -> None:
        hl = int(self.hist_len[i])
        n = min(len(toks), self._hist_cap - hl)
        if n > 0:
            self.hist[i, hl:hl + n] = toks[:n]
            self.hist_len[i] = hl + n

    def _finish_check(self, i: int, req: Request, last_tok: int,
                      cursor_i: int, finished: list) -> None:
        hit_eos = self.eos_id is not None and last_tok == self.eos_id
        budget_full = len(req.out_tokens) >= req.max_new_tokens
        arena_full = cursor_i >= self.cache_len
        if hit_eos or budget_full or arena_full:
            req.done = True
            if arena_full and not (hit_eos or budget_full):
                # retired by KV exhaustion, not by its own budget or an
                # EOS: flag it so callers can tell a complete answer from
                # a clipped one instead of silently receiving fewer tokens
                req.truncated = True
                self.truncations += 1
            finished.append(req)
            self.active[i] = None
            self.live[i] = False

    # -- one decode step for every live slot ----------------------------------
    def step(self) -> list:
        finished = self._admit()
        if self.paged_kv and self.live.any():
            finished.extend(self._retire_pool_exhausted())
        if not self.live.any():
            return finished
        ahead = self._may_look_ahead()
        with tracing.span("decode", live=int(np.count_nonzero(self.live)),
                          ahead=int(ahead)):
            if self.spec_decode:
                finished.extend(self._step_spec())
            else:
                finished.extend(self._step_one(ahead))
        return finished

    def _may_look_ahead(self) -> bool:
        """Whether the step after the one this call reads may be dispatched
        before that read.  Only on the contiguous one-token path (the paged
        dispatch needs the host allocator's view of this step's retirements,
        a speculative one its accepted counts), and only while every slot is
        live and no request this step serves is known to retire by it (its
        budget or a full arena): nothing could be admitted at the next
        boundary, so admission sees the state a synchronous step leaves.  An
        EOS cannot be known; its slot's lookahead token is dropped."""
        if self.paged_kv or self.spec_decode or not self.live.all():
            return False
        cur = self._ahead
        served = self.active if cur is None else cur.served
        cursor = self._cursor + 1 if cur is None else cur.cursor
        for i, req in enumerate(self.active):
            if served[i] is req and (
                    len(req.out_tokens) + 1 >= req.max_new_tokens
                    or cursor[i] >= self.cache_len):
                return False
        return True

    def _dispatch_one(self) -> _Dispatched:
        if self.paged_kv:
            self._apply_paged_alloc()
            nxt, self.cache = tm.paged_serve_step(
                self.params, self.cache, self.cur_tok, self._live_mask(),
                self.cfg, self.block_size,
            )
        else:
            nxt, self.cache = tm.serve_step(
                self.params, self.cache, self.cur_tok, self.cfg
            )
        self.cur_tok = nxt
        self._cursor += 1  # decode_step advances every slot's cursor
        return _Dispatched(nxt, tuple(self.active), self._cursor.copy())

    def _step_one(self, ahead: bool) -> list:
        """One-token decode: one jitted dispatch emits one token per slot.
        Reads the step dispatched ahead by the previous call, if any, else
        dispatches one; with ``ahead`` it first queues the next step behind
        it.  A step still on the chip when this returns is the next call's
        read, and serves at least one live request: when every request it
        served has retired it is dropped, so an idle engine has none."""
        cur = self._ahead
        if cur is None:
            cur = self._dispatch_one()
        else:
            self.ahead_steps += 1
        self._ahead = self._dispatch_one() if ahead else None
        finished = []
        live_before = self.live.copy()
        with tracing.span("decode.wait"):
            toks = np.asarray(cur.nxt)
        self.decode_steps += 1
        for i, req in enumerate(cur.served):
            if req is None or self.active[i] is not req:
                continue  # retired by an EOS after this step was dispatched
            t = int(toks[i])
            req.out_tokens.append(t)
            self.emitted_tokens += 1
            self.decode_tokens += 1
            self.slot_steps += 1
            self._hist_append(i, [t])
            self._finish_check(i, req, t, int(cur.cursor[i]), finished)
        if self.paged_kv:
            self._release_retired(live_before)
        queued = self._ahead
        if queued is not None and not any(
                r is not None and r is a
                for r, a in zip(queued.served, self.active)):
            self._ahead = None  # every request it served retired: drop it
        return finished

    def _step_spec(self) -> list:
        """Self-speculative decode: draft ``W-1`` tokens per slot from its
        own history, verify all of them, and commit the greedy-matching
        prefix (1..W tokens per slot) — all in ONE jitted dispatch."""
        w = self.draft_window
        # acceptance room is computed in-jit from the device mirrors; both
        # terms are >= 1 for a live slot (admission retires len >= max_new
        # immediately, decode retires cursor >= cache_len).  Dead slots run
        # with whatever stale room their mirrors imply (clamped >= 1, so up
        # to W of drift per step) — harmless: writes stay masked at the
        # arena edge and admission re-pins cursor/mirrors before reuse
        if self.paged_kv:
            self._apply_paged_alloc()
            (packed, self.cur_tok, self.cache, self._hist_dev,
             self._hist_len_dev, self._out_len_dev) = _paged_spec_step(
                self.params, self.cache, self.cur_tok, self._hist_dev,
                self._hist_len_dev, self._max_new_dev, self._out_len_dev,
                self._live_mask(), self.cfg, w - 1, self.eos_id,
                self.block_size,
            )
        else:
            (packed, self.cur_tok, self.cache, self._hist_dev,
             self._hist_len_dev, self._out_len_dev) = _spec_step(
                self.params, self.cache, self.cur_tok, self._hist_dev,
                self._hist_len_dev, self._max_new_dev, self._out_len_dev,
                self.cfg, w - 1, self.eos_id,
            )
        self.decode_steps += 1
        finished = []
        live_before = self.live.copy()
        with tracing.span("decode.wait"):
            packed_np = np.asarray(packed)  # the step's single host sync
        g_np, acc_np = packed_np[:, :w], packed_np[:, w]
        self._cursor += acc_np  # verify_step advanced every slot by accepted
        self._out_len += acc_np  # keep the host mirror bitwise in step
        for i, req in enumerate(self.active):
            if req is None or not self.live[i]:
                continue
            a = int(acc_np[i])
            emitted = g_np[i, :a].tolist()
            req.out_tokens.extend(emitted)
            self.emitted_tokens += a
            self.decode_tokens += a
            self.slot_steps += 1
            self.draft_proposed += w - 1
            self.draft_accepted += a - 1
            self._hist_append(i, emitted)
            self._finish_check(i, req, emitted[-1], int(self._cursor[i]),
                               finished)
        if self.paged_kv:
            self._release_retired(live_before)
        return finished

    def decode_stats(self) -> dict:
        """Dispatch-amortization telemetry.  ``tokens_per_step`` is the mean
        number of tokens a live slot commits per jitted decode dispatch —
        exactly 1.0 in one-token mode, up to ``draft_window`` under
        speculation — i.e. the accepted-tokens/step signal, normalized per
        slot so batch occupancy does not inflate it.  ``decode_steps`` counts
        the steps whose tokens were read; ``ahead_share`` is the part of them
        dispatched before the previous step was read.  A step still on the
        chip belongs to live requests and is counted when ``step`` reads it;
        an idle engine (every exit: drained, run to completion, aborted)
        holds none."""
        stats = {
            "spec_decode": self.spec_decode,
            "draft_window": self.draft_window if self.spec_decode else 1,
            "decode_steps": self.decode_steps,
            "emitted_tokens": self.emitted_tokens,
            "decode_tokens": self.decode_tokens,
            "draft_proposed": self.draft_proposed,
            "draft_accepted": self.draft_accepted,
            "tokens_per_step": self.decode_tokens / max(self.slot_steps, 1),
            "draft_accept_rate": (
                self.draft_accepted / self.draft_proposed
                if self.draft_proposed else 0.0
            ),
            "ahead_steps": self.ahead_steps,
            "ahead_share": self.ahead_steps / max(self.decode_steps, 1),
            "paged_kv": self.paged_kv,
            "truncations": self.truncations,
            "prefix_share": self.prefix_share,
            "prefill_batches": self.prefill_batches,
            "prefill_rows": self.prefill_rows,
            "admit_seconds": self.admit_seconds,
        }
        if getattr(self.cache, "routed", None) is not None:
            # the held experts' routing counters: one device read, here only
            stats.update(
                routed_per_expert=np.asarray(self.cache.routed).tolist(),
                max_expert_load=int(self.cache.max_load),
            )
        if self.paged_kv:
            stats.update(
                block_size=self.block_size,
                pool_blocks=self.pool_blocks,
                pool_high_water_blocks=self.pool_high_water,
                pool_free_blocks=self._free_host,
                kv_shared_admits=self.kv_shared_admits,
                kv_reused_tokens=self.kv_reused_tokens,
                kv_cow_copies=self.kv_cow_copies,
                kv_pins=self.kv_pins,
                kv_releases=self.kv_releases,
                kv_pinned_blocks=self.kv_pinned_blocks,
            )
        return stats

    def stats_ns(self) -> dict:
        """Namespaced stats (unified serving schema): the decode arena's
        counters under ``decode.*`` — see :mod:`repro.serving.stats`."""
        return {"decode": self.decode_stats()}

    def run_to_completion(self, max_steps: int = 10_000) -> list:
        """Step until every request drains.  Raises if ``max_steps`` elapse
        with work still queued or live, instead of silently returning a
        partial result set."""
        done = []
        for _ in range(max_steps):
            done.extend(self.step())
            if not self.queue and not self.live.any():
                return done
        raise RuntimeError(
            f"run_to_completion: work still pending after {max_steps} steps "
            f"({len(self.queue)} queued, {int(self.live.sum())} live slots)"
        )
