"""Retrieval cache for the fused RAG serving engine.

A policy-driven map from *quantized query embedding* to the finished
retrieval result (filtered subgraph membership + seed ids).  Quantization
(``round(emb / eps)``) makes near-duplicate queries — repeated questions,
embedding jitter below ``eps`` — collapse onto one key, so a hit skips the
entire index + BFS + filter stack.  Entries are host-side numpy (small:
O(budget) ints per query), so the cache never holds device memory.

Eviction policies (capacity pressure):

* ``lru`` — evict the least-recently-used entry (hits refresh recency).
* ``lfu`` — evict the entry with the fewest per-entry hits; ties broken by
  least-recent, so a cold newcomer never outlives a warm regular.
* ``ttl`` — evict the oldest-inserted entry (insertion-order FIFO); pairs
  naturally with an expiry window.

Independently of the policy, an optional ``ttl`` (seconds) expires entries
``ttl`` after insertion: an expired entry is *invisible* to ``get`` (each
such lookup counts a miss + ``expired``) but stays resident until capacity
pressure reclaims it — ``put`` purges expired entries before falling back
to policy eviction.  Keeping the stale bytes around is deliberate: the
serving engine's graceful-degradation ladder (:mod:`repro.serving.rag_engine`)
falls back to a stale entry via :meth:`RetrievalCache.peek_stale` when live
retrieval fails and retries are exhausted — a TTL-expired answer beats no
answer.

For async admission prefetch the cache also tracks an **in-flight miss
registry**: keys whose retrieval has been dispatched but whose results have
not been collected yet.  A later admission launch consults it so a
retrieved-but-not-yet-collected query is never re-dispatched — the request
defers to the in-flight wave instead (see
:class:`repro.serving.prefetch.AdmissionPrefetcher`).  Each in-flight key
may carry the *owner wave's* ``entries_by_key`` dict (filled in place at
that wave's collect), which is what makes the protocol work **across
replicas sharing one cache**: a prefetcher that finds a key in flight but
owned by none of its own waves can still defer — single-flight semantics
for the whole replica fleet, one dispatch per unique query no matter which
replica's request arrives first (see
:class:`repro.serving.router.ReplicaRouter`).

When the corpus mutates under serving (:mod:`repro.core.mutation`), the
cache is **versioned**: every entry records the mutation ``epoch`` it was
retrieved against plus its ``region`` (the set of node-id buckets its
subgraph + seeds touch), and :meth:`RetrievalCache.invalidate_regions`
drops only the entries whose region a mutation touched — releasing any
prefix-sharing KV pins they hold — while entries over unrelated regions
survive the epoch bump.  ``put`` refuses results collected against a
superseded region (an in-flight wave that raced a mutation), so staleness
for touched regions is bounded by a single epoch.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict

import numpy as np

POLICIES = ("lru", "lfu", "ttl")
# query-embedding quantization step of the cache key: embeddings within
# the same multiple of it in every coordinate share an entry
QUANT_EPS = 1e-3


@dataclasses.dataclass
class CachedRetrieval:
    """One query's retrieval output, materialized on host.

    When prefix sharing is on (``RGL_PREFIX_SHARE``), a hot entry may
    additionally *pin* the paged-KV pool blocks holding the prefilled
    prompt that this retrieval produced: ``kv_blocks`` names the pool
    block ids (the pin holds one refcount per block), ``kv_prompt`` the
    exact token ids those blocks cover (admission re-validates against
    it), ``kv_first_tok`` the prefill's recorded argmax, and
    ``kv_release`` the owning engine's release hook — called by the cache
    on eviction/overwrite and by ``reclaim_kv`` under pool pressure, so
    cache lifetime, not request lifetime, bounds how long prefilled KV
    stays resident."""

    nodes: np.ndarray  # (M,) int32 subgraph node ids (sentinel where ~mask)
    mask: np.ndarray  # (M,) bool
    dist: np.ndarray  # (M,) int32 hop distances
    seeds: np.ndarray  # (S,) int32 seed node ids
    # graph-mutation versioning (see RetrievalCache.invalidate_regions):
    # the mutation epoch this retrieval ran against, and the set of
    # node-id buckets its subgraph + seeds touch (computed by put()).
    epoch: int = 0
    region: frozenset | None = None
    # prefilled-KV pin (engine-owned; None/defaults when unpinned)
    kv_blocks: np.ndarray | None = None  # (nblk,) int32 pool block ids
    kv_len: int = 0  # prompt tokens the pinned blocks cover
    kv_first_tok: int = -1  # prefill argmax recorded at pin time
    kv_prompt: np.ndarray | None = None  # (L,) int32 exact pinned prompt
    kv_owner: object = None  # engine whose pool the block ids index
    kv_release: object = None  # hook: entry -> blocks returned to the pool
    cache_key: bytes | None = None  # set by put(); drives is_resident()


@dataclasses.dataclass
class _Slot:
    """Cache bookkeeping around one entry."""

    entry: CachedRetrieval
    hits: int = 0  # per-entry hit count (drives lfu)
    inserted_at: float = 0.0  # ttl expiry + FIFO eviction order
    # each entry's TTL expiry is counted in stats()["expired"] exactly
    # once (the first lookup or purge that observes it) — the counter
    # tracks distinct expiries, not lookups of an expired resident
    expired_counted: bool = False


class RetrievalCache:
    """Policy-driven cache keyed on quantized query embeddings.

    ``get`` counts a hit or miss (expired entries are dropped and count as
    misses) and refreshes recency; ``put`` inserts and evicts per the
    policy beyond ``capacity``.  ``capacity <= 0`` disables caching (every
    lookup is a miss, nothing is stored).  ``now_fn`` is injectable so TTL
    behavior is testable without sleeping.
    """

    def __init__(
        self,
        capacity: int = 256,
        *,
        policy: str = "lru",
        ttl: float | None = None,
        region_bucket: int = 32,
        mutation_flush: str = "region",
        now_fn=time.monotonic,
    ):
        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, got {policy!r}")
        if mutation_flush not in ("region", "all"):
            raise ValueError(
                f"mutation_flush must be 'region' or 'all', got "
                f"{mutation_flush!r}"
            )
        self.capacity = capacity
        self.policy = policy
        self.ttl = ttl
        self.region_bucket = max(1, int(region_bucket))
        self.mutation_flush = mutation_flush
        self._now = now_fn
        self._data: OrderedDict[bytes, _Slot] = OrderedDict()  # recency order
        # dispatched-but-uncollected keys -> owner wave's entries_by_key dict
        # (None for owners that did not register one)
        self._inflight: dict[bytes, dict | None] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0  # capacity evictions by the active policy
        self.expired = 0  # ttl expiries
        self.stale_hits = 0  # peek_stale found a resident (possibly
        #                      TTL-expired) entry to degrade onto
        self.stale_misses = 0  # peek_stale found nothing resident
        # graph-mutation versioning: the newest epoch a mutation has
        # reached, and a bounded log of (epoch, touched buckets) so put()
        # can reject results computed against a superseded region.
        self.graph_epoch = 0
        self._touched_log: list[tuple[int, frozenset]] = []
        self._touched_log_max = 256
        self.invalidated = 0  # entries dropped by invalidate_regions
        self.stale_rejects = 0  # put() refused a superseded-region entry

    def __len__(self) -> int:
        return len(self._data)

    def key(self, query_emb) -> bytes:
        q = np.asarray(query_emb, np.float32).ravel()
        return np.round(q / QUANT_EPS).astype(np.int32).tobytes()

    # -- in-flight miss registry ----------------------------------------------
    def mark_inflight(self, key: bytes, entries: dict | None = None) -> None:
        """Record that ``key``'s retrieval has been dispatched but not yet
        collected, so later admission launches defer instead of re-dispatch.

        ``entries`` (optional) is the owning wave's ``entries_by_key`` dict,
        filled in place at that wave's collect — registering it lets a
        *different* prefetcher sharing this cache defer to the owner too
        (cross-replica single flight)."""
        self._inflight[key] = entries

    def is_inflight(self, key: bytes) -> bool:
        return key in self._inflight

    def inflight_entries(self, key: bytes) -> dict | None:
        """The registered owner's ``entries_by_key`` dict for an in-flight
        ``key`` (None if the key is not in flight, or its owner registered
        no dict).  Cross-replica deferral resolves through this."""
        return self._inflight.get(key)

    def release_inflight(self, key: bytes) -> None:
        self._inflight.pop(key, None)

    @property
    def inflight_count(self) -> int:
        return len(self._inflight)

    # -- expiry ---------------------------------------------------------------
    def _is_expired(self, slot: _Slot, now: float) -> bool:
        return self.ttl is not None and now - slot.inserted_at > self.ttl

    def _count_expiry(self, slot: _Slot) -> None:
        if not slot.expired_counted:
            slot.expired_counted = True
            self.expired += 1

    def _purge_expired(self, now: float) -> None:
        dead = [k for k, s in self._data.items() if self._is_expired(s, now)]
        for k in dead:
            slot = self._data.pop(k)
            self._count_expiry(slot)
            self._release_kv(slot.entry)

    # -- lookup / insert ------------------------------------------------------
    def get(self, query_emb) -> CachedRetrieval | None:
        k = self.key(query_emb)
        slot = self._data.get(k)
        now = self._now()
        if slot is not None and self._is_expired(slot, now):
            # expired entries are invisible here but stay resident (until a
            # capacity-pressure purge) so peek_stale can serve them when
            # live retrieval fails — see the degradation ladder.  The expiry
            # is counted once per entry, however many lookups observe it.
            self._count_expiry(slot)
            self.misses += 1
            return None
        if slot is None:
            self.misses += 1
            return None
        self._data.move_to_end(k)
        slot.hits += 1
        self.hits += 1
        return slot.entry

    def peek_stale(self, query_emb) -> CachedRetrieval | None:
        """Degraded-mode lookup: return the resident entry for this key even
        if TTL-expired, without touching hit/miss counters or recency.  The
        serving engine falls back to this when live retrieval has failed and
        retries are exhausted (counted there as ``stale_served``).  Counted
        here as ``stale_hits`` / ``stale_misses`` so degraded serving is
        observable at the cache tier too — with several engines sharing one
        cache, the cache-level totals are the fleet-wide view."""
        slot = self._data.get(self.key(query_emb))
        if slot is None:
            self.stale_misses += 1
            return None
        self.stale_hits += 1
        return slot.entry

    def hit_count(self, query_emb) -> int:
        """Per-entry hit count (0 if absent) — the lfu eviction signal."""
        slot = self._data.get(self.key(query_emb))
        return slot.hits if slot is not None else 0

    @staticmethod
    def _release_kv(entry: CachedRetrieval) -> int:
        """Release an entry's prefilled-KV pin (if any) as it leaves the
        cache — eviction, TTL purge, or overwrite — so cache pressure frees
        pool blocks.  The hook is the owning engine's and idempotent."""
        rel = getattr(entry, "kv_release", None)
        return int(rel(entry)) if rel is not None else 0

    def _evict_one(self, protect: bytes) -> None:
        # the just-inserted key is never its own victim (else a 0-hit
        # newcomer would be evicted immediately under lfu)
        pool = [k for k in self._data if k != protect]
        if self.policy == "lru":
            victim = pool[0]  # OrderedDict order = least recent first
        elif self.policy == "lfu":
            # fewest hits; scan in recency order so ties evict least-recent
            victim = min(pool, key=lambda k: self._data[k].hits)
        else:  # ttl: oldest inserted first (insertion-order FIFO)
            victim = min(pool, key=lambda k: self._data[k].inserted_at)
        self._release_kv(self._data.pop(victim).entry)
        self.evictions += 1

    # -- graph-mutation versioning --------------------------------------------
    def _region_of(self, entry: CachedRetrieval) -> frozenset:
        """Node-id buckets an entry's subgraph + seeds touch."""
        nodes = np.asarray(entry.nodes)[np.asarray(entry.mask, bool)]
        ids = np.concatenate([nodes.ravel(), np.asarray(entry.seeds).ravel()])
        return frozenset((ids.astype(np.int64) // self.region_bucket).tolist())

    def _conflicts_since(self, epoch: int, region: frozenset | None) -> bool:
        """Did any mutation after ``epoch`` touch ``region``?  Conservative:
        an epoch older than the bounded log (or an unknown region) counts
        as a conflict."""
        if self._touched_log and epoch < self._touched_log[0][0] - 1:
            return True
        for e, touched in self._touched_log:
            if e <= epoch:
                continue
            if region is None or (region & touched):
                return True
        return False

    def invalidate_regions(self, touched_nodes, epoch: int) -> int:
        """A mutation reached ``epoch`` after touching ``touched_nodes``:
        drop every entry whose subgraph region intersects the touched
        buckets (releasing any prefilled-KV pin it holds) so no future
        lookup — including degraded-mode ``peek_stale`` — can serve a
        result the mutation superseded.  Entries in unrelated regions
        survive; ``mutation_flush="all"`` is the strict mode that drops
        everything.  Returns the number of entries invalidated.

        Mutations that only *add* nodes/edges near a cached subgraph also
        land in the touched set (endpoints count), so a cached result that
        *should* now include a new neighbor is invalidated too — staleness
        is bounded by one epoch for touched regions.
        """
        ids = np.asarray(touched_nodes, np.int64).ravel()
        buckets = frozenset((ids // self.region_bucket).tolist())
        self.graph_epoch = max(self.graph_epoch, int(epoch))
        self._touched_log.append((int(epoch), buckets))
        del self._touched_log[: -self._touched_log_max]
        victims = []
        for k, slot in self._data.items():
            region = slot.entry.region
            if self.mutation_flush == "all" or region is None \
                    or (region & buckets):
                victims.append(k)
        for k in victims:
            self._release_kv(self._data.pop(k).entry)
        self.invalidated += len(victims)
        return len(victims)

    def put(self, query_emb, entry: CachedRetrieval) -> None:
        if self.capacity <= 0:
            return
        if entry.region is None:
            entry.region = self._region_of(entry)
        if entry.epoch < self.graph_epoch and \
                self._conflicts_since(entry.epoch, entry.region):
            # collected after a mutation superseded its region (e.g. an
            # in-flight wave launched pre-mutation): still served to its
            # requester, never cached
            self.stale_rejects += 1
            return
        now = self._now()
        k = self.key(query_emb)
        prev = self._data.get(k)
        if prev is not None:
            # re-insert of a live key (e.g. prefetch.py re-publishing an
            # owner-computed entry the owner's own eviction raced away):
            # keep the accumulated ``hits`` so a warm lfu entry does not
            # become the next eviction victim.  ``inserted_at`` DOES
            # refresh — a re-insert carries fresh data, so its TTL window
            # restarts (and ttl-policy eviction treats it as newest).
            if prev.entry is not entry:
                self._release_kv(prev.entry)  # displaced entry's pin goes
            self._data[k] = _Slot(entry=entry, inserted_at=now,
                                  hits=prev.hits)
        else:
            self._data[k] = _Slot(entry=entry, inserted_at=now)
        entry.cache_key = k
        self._data.move_to_end(k)
        if len(self._data) > self.capacity:
            self._purge_expired(now)
        while len(self._data) > self.capacity:
            self._evict_one(protect=k)

    # -- prefilled-KV pins ----------------------------------------------------
    def is_resident(self, entry: CachedRetrieval) -> bool:
        """True while ``entry`` is the live occupant of its cache slot —
        the engine's pin gate, so prompt blocks are never pinned to an
        entry that eviction (or an overwrite) already displaced (such a pin
        would leak pool blocks: no future eviction would release it)."""
        k = getattr(entry, "cache_key", None)
        if k is None:
            return False
        slot = self._data.get(k)
        return slot is not None and slot.entry is entry

    def kv_pinned_entries(self) -> int:
        return sum(
            1 for s in self._data.values()
            if getattr(s.entry, "kv_blocks", None) is not None
        )

    def reclaim_kv(self, want_blocks: int, owner=None) -> int:
        """Release prefilled-KV pins until at least ``want_blocks`` pool
        blocks have returned to the free stack (or no pins remain) —
        the engine's pool-pressure hook, called *before* it truncates any
        live request, so pinned KV is strictly lower-priority than live
        decode.  Victim order: TTL-expired pins first, then the active
        policy's eviction order among the rest.  Entries keep their
        retrieval result — only the KV pin is dropped.  ``owner`` filters
        to pins held against one engine's pool (a shared cache may carry
        pins from several replicas)."""
        if want_blocks <= 0:
            return 0
        now = self._now()
        pinned = [
            k for k, s in self._data.items()
            if getattr(s.entry, "kv_blocks", None) is not None
            and (owner is None or s.entry.kv_owner is owner)
        ]
        expired = [k for k in pinned
                   if self._is_expired(self._data[k], now)]
        fresh = [k for k in pinned if k not in set(expired)]
        if self.policy == "lfu":
            fresh.sort(key=lambda k: self._data[k].hits)
        elif self.policy == "ttl":
            fresh.sort(key=lambda k: self._data[k].inserted_at)
        # lru: dict order is already least-recent-first
        freed = 0
        for k in expired + fresh:
            if freed >= want_blocks:
                break
            freed += self._release_kv(self._data[k].entry)
        return freed

    def stats(self) -> dict:
        total = self.hits + self.misses
        now = self._now()
        resident = len(self._data)
        live = sum(1 for s in self._data.values()
                   if not self._is_expired(s, now))
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "expired": self.expired,
            "stale_hits": self.stale_hits,
            "stale_misses": self.stale_misses,
            "policy": self.policy,
            # resident = entries occupying capacity (including TTL-expired
            # ones kept for degraded-mode peek_stale); live = entries a
            # get() could still hit.  "size" keeps its historical meaning
            # (resident) for existing dashboards.
            "size": resident,
            "resident": resident,
            "live": live,
            "kv_pinned_entries": self.kv_pinned_entries(),
            "inflight": len(self._inflight),
            "hit_rate": self.hits / total if total else 0.0,
            "graph_epoch": self.graph_epoch,
            "invalidated": self.invalidated,
            "stale_rejects": self.stale_rejects,
        }

    def stats_ns(self) -> dict:
        """Namespaced stats (unified serving schema): this cache's counters
        under ``cache.*`` — see :mod:`repro.serving.stats`."""
        return {"cache": self.stats()}
