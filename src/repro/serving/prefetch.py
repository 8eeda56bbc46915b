"""Double-buffered async admission retrieval for the fused RAG engine.

The sync serving path retrieves at wave boundaries: every admission wave
dispatches one jitted ``retrieve_many`` and immediately forces the result to
host (``np.asarray``), so the decode arena idles for the full retrieval
latency of every wave.  :class:`AdmissionPrefetcher` splits that into two
phases so wave *i+1*'s retrieval overlaps wave *i*'s decode steps:

* **launch** — cache lookup + intra-wave dedupe + ONE jitted
  ``RGLPipeline.retrieve_many`` dispatch.  Results are kept as *device
  arrays* (JAX async dispatch: the call returns before the computation
  finishes), so retrieval runs concurrently with whatever the engine does
  next — i.e. decode steps for the previous wave.
* **collect** — block on the device arrays (the only host sync), insert the
  finished entries into the :class:`~repro.serving.cache.RetrievalCache`,
  and hand ``(request, entry)`` pairs back for tokenization + admission.
  The engine runs collect only once decode slots free up.

Between launch and collect every miss key is marked *in-flight* on the
cache (``mark_inflight``), so a later launch never re-dispatches a query
that is retrieved-but-not-yet-collected: the request **defers** to the
owning wave and resolves — including its cache-hit accounting — at its own
wave's collect.  This keeps hit/miss totals identical to the sync schedule.

``depth`` bounds how many launched-but-uncollected waves may exist (the
backpressure window).  The serving default is 1 — classic double buffering:
one wave decoding, one wave retrieving.  ``depth >= 2`` pipelines multiple
retrieval waves and is where the in-flight set becomes load-bearing.

**Parity scope.**  At the default ``depth=1`` every launch happens after all
earlier collects, so cache state — contents, recency, per-entry hits — is
step-for-step identical to sync and parity is unconditional.  At
``depth >= 2`` wave *i+1*'s lookups intentionally run before wave *i*'s
puts (that is the pipelining); outputs stay bitwise identical, and hit/miss
totals still match except under capacity pressure, where the reordered
recency updates can pick different eviction victims than the sync schedule
would.  Serializing the lookups would restore that last corner but forfeit
the overlap, so the divergence is accepted and documented.

Telemetry (merged into ``RAGServeEngine.stats()``):

* ``waves`` / ``batches`` / ``queries`` — async-collected waves that
  dispatched a retrieval (miss-free waves are excluded — they have nothing
  in flight), retrieval dispatches, retrieved (deduped) queries.
* ``launch_seconds`` / ``block_seconds`` — host time in dispatch and in the
  collect-phase force; their sum is the *observable* retrieval cost.
* ``overlap_seconds`` — per-wave wall time between launch returning and
  collect starting: the window retrieval had to run behind decode.  This is
  an *upper bound* on hidden retrieval compute — if retrieval finished
  early, the tail of the window hid nothing.
* ``overlap_steps`` — engine steps executed between a wave's launch and its
  collect (the overlap-oracle signal).
* ``overlap_tokens`` — tokens *committed* by the engine between a wave's
  launch and its collect.  Under self-speculative decode one engine step
  commits up to ``draft_window`` tokens, so steps systematically undercount
  the decode work that hid the retrieval; accepted tokens are the
  schedule-invariant measure.
* ``hidden_frac`` — ``overlap / (overlap + block)``: the fraction of each
  wave's in-flight window not paid as blocking time.  Near 1.0 means
  retrieval was never the bottleneck (either genuinely hidden or simply
  cheap); judge the magnitude of the win from ``collect_block_seconds``
  against the sync schedule's ``retrieval_seconds``.

**Fault tolerance.**  Retrieval is a fallible, variable-latency stage, so
the collect phase carries a containment layer (all off by default):

* a wave whose arrays are not ready ``retrieval_timeout_s`` after its
  dispatch is declared timed out instead of blocked on forever;
* a failed wave — launch raise, force raise, timeout, or a row whose node
  ids fail validation (out of ``[0, n_nodes)`` under the mask) — relaunches
  **only its failed miss-groups**, each as its own size-1 dispatch, up to
  ``max_retries`` times with exponential ``retry_backoff_s`` backoff.
  Size-1 relaunches are the per-request isolation mechanism: one poison row
  can no longer doom its wave-mates' retries, and retrieval is row-
  independent so a size-1 result is bitwise identical to the row it would
  have occupied in the batch;
* a group that exhausts its retries *fails closed*: its requests come back
  with ``entry=None`` plus an error reason (the engine's degradation
  ladder takes it from there) — ``collect`` itself never raises for a
  retrieval fault, and the wave's in-flight cache keys are always released
  in a ``finally`` so no key is poisoned and no later wave defers to a
  dead owner.  A deferred request whose owner's group failed (or whose
  owner aborted) re-dispatches as its own size-1 group instead of waiting
  forever.

Counters: ``retries`` (relaunches), ``timeouts`` (timed-out waits),
``failures`` (groups that exhausted retries and went to the ladder).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import numpy as np

from repro import tracing
from repro.serving.cache import CachedRetrieval, RetrievalCache


@dataclasses.dataclass
class PrefetchWave:
    """One launched admission wave: requests + the uncollected device arrays."""

    reqs: list  # RAGRequest, arrival order
    entry_for: list  # per request: CachedRetrieval | None until resolved
    miss_groups: dict  # key -> [request indices], intra-wave dedupe
    deferred: list  # (request idx, key, owner wave's entries_by_key dict)
    sub: object = None  # Subgraph of device arrays (lazy) when misses exist
    seeds: object = None
    epoch: int = 0  # graph epoch the retrieval was launched against
    launched_at: float = 0.0  # clock at dispatch return
    launch_step: int = 0  # engine step counter at launch
    launch_tokens: int = 0  # engine emitted-token counter at launch
    entries_by_key: dict = dataclasses.field(default_factory=dict)
    launch_error: Optional[str] = None  # the batched dispatch itself raised
    error_for: list = dataclasses.field(default_factory=list)  # per request

    @property
    def has_misses(self) -> bool:
        return bool(self.miss_groups)


class AdmissionPrefetcher:
    """Launch/collect state machine over at most ``depth`` in-flight waves.

    The same launch/collect code drives both admission schedules: sync mode
    collects immediately after launch (blocking at the wave boundary, zero
    overlap by definition), prefetch mode leaves the wave in flight until
    the engine has free slots.
    """

    def __init__(
        self,
        pipeline,
        cache: RetrievalCache,
        *,
        wave_size: int,
        depth: int = 1,
        retrieval_timeout_s: Optional[float] = None,
        max_retries: int = 0,
        retry_backoff_s: float = 0.0,
        now_fn: Callable[[], float] = time.perf_counter,
        sleep_fn: Callable[[float], None] = time.sleep,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if retrieval_timeout_s is not None and retrieval_timeout_s <= 0:
            raise ValueError(
                f"retrieval_timeout_s must be > 0, got {retrieval_timeout_s}"
            )
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.pipeline = pipeline
        self.cache = cache
        self.wave_size = wave_size
        self.depth = depth
        self.retrieval_timeout_s = retrieval_timeout_s
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self._now = now_fn
        self._sleep = sleep_fn
        self._waves: deque[PrefetchWave] = deque()
        # telemetry
        self.waves = 0  # async-collected waves (prefetch schedule only)
        self.batches = 0  # retrieval dispatches (both schedules)
        self.queries = 0  # deduped queries retrieved
        self.launch_seconds = 0.0
        self.block_seconds = 0.0
        self.overlap_seconds = 0.0
        self.overlap_steps = 0
        self.overlap_tokens = 0
        self.retries = 0  # size-1 relaunches of failed miss-groups
        self.timeouts = 0  # waits that hit retrieval_timeout_s
        self.failures = 0  # groups that exhausted retries (ladder-bound)

    @property
    def _n_nodes(self) -> Optional[int]:
        """Node-id validation bound for corrupt-result detection; ``None``
        skips the check.  Read per use (not cached at construction) so the
        bound tracks the live graph as online mutations add nodes."""
        n = getattr(self.pipeline, "n_valid_nodes", None)
        if n is not None:
            return int(n)
        emb = getattr(self.pipeline, "node_emb", None)
        return int(emb.shape[0]) if emb is not None else None

    @property
    def in_flight(self) -> int:
        return len(self._waves)

    @property
    def in_flight_requests(self) -> int:
        """Requests inside launched-but-uncollected waves (load signal for
        the replica router's health snapshot)."""
        return sum(len(w.reqs) for w in self._waves)

    def can_launch(self) -> bool:
        return len(self._waves) < self.depth

    def launched_before(self, step: int) -> bool:
        """Whether the oldest in-flight wave was launched before ``step`` —
        collecting a wave in the same step it launched forfeits its overlap
        window, so the engine only does that when the arena is idle."""
        return bool(self._waves) and self._waves[0].launch_step < step

    def _owner_entries(self, key: bytes) -> Optional[dict]:
        """The in-flight owner wave's (still-empty) entries_by_key dict —
        filled in place at that wave's collect, so holding the dict (not the
        wave) is enough for deferred fallback and retains nothing else."""
        for w in self._waves:
            if key in w.miss_groups:
                return w.entries_by_key
        return None

    # -- launch ---------------------------------------------------------------
    def launch(self, reqs: list, *, step: int = 0,
               tokens: int = 0) -> PrefetchWave:
        """Dispatch one admission wave without forcing any device array.

        Cache lookups and hit/miss accounting happen here, mirroring the
        sync schedule request-for-request: hits attach immediately, misses
        dedupe into one ``retrieve_many`` row per quantized key (every
        duplicate still counts its own miss, as in sync admission), and
        keys already in flight defer to the owning wave with no counter
        touched until that wave collects.  Every request is stamped
        ``launched_at`` when the dispatch returns.
        """
        with tracing.span("retrieval.launch"):
            return self._launch(reqs, step, tokens)

    def _launch(self, reqs: list, step: int, tokens: int) -> PrefetchWave:
        cache = self.cache
        t0 = self._now()
        wave = PrefetchWave(
            reqs=reqs, entry_for=[None] * len(reqs), miss_groups={},
            deferred=[], launch_step=step, launch_tokens=tokens,
        )
        for j, r in enumerate(reqs):
            k = cache.key(r.query_emb)
            if k in wave.miss_groups:  # intra-wave dup: miss, one dispatch row
                cache.get(r.query_emb)  # counts the duplicate's miss
                wave.miss_groups[k].append(j)
                continue
            if cache.is_inflight(k):  # owned by an earlier uncollected wave
                owner_entries = self._owner_entries(k)
                if owner_entries is None:
                    # not one of OUR waves — with a shared cache the owner
                    # may be another replica's prefetcher, which registered
                    # its entries_by_key dict at mark_inflight: defer to it
                    # exactly like an intra-engine owner (cross-replica
                    # single flight — one dispatch per unique query across
                    # the whole fleet)
                    owner_entries = cache.inflight_entries(k)
                if owner_entries is not None:
                    wave.deferred.append((j, k, owner_entries))
                    continue
                # in-flight marker with no registered owner anywhere: a
                # stale key from a dead engine that never collected — fall
                # through and treat as an ordinary miss so the query is
                # re-dispatched instead of deferring to a result that will
                # never arrive
            e = cache.get(r.query_emb)
            if e is not None:
                wave.entry_for[j] = e
                r.cache_hit = True
            else:
                wave.miss_groups[k] = [j]

        if wave.miss_groups:
            qe = np.stack(
                [reqs[idxs[0]].query_emb for idxs in wave.miss_groups.values()]
            ).astype(np.float32)
            # async dispatch: retrieve_many returns device arrays without a
            # host sync, so the scan/BFS/filter pipeline runs concurrently
            # with the decode steps the engine issues after this returns
            try:
                res = self.pipeline.retrieve_many(qe, batch_size=self.wave_size)
                wave.sub, wave.seeds = res.sub, res.seeds
                wave.epoch = res.epoch
                n_valid = res.n_valid
            except Exception as exc:  # data-plane fault: contained, retried
                # at collect (per-group, size-1) — never marked in-flight,
                # so a concurrent wave is free to dispatch the same key
                wave.launch_error = f"dispatch: {exc}"
            else:
                # mark only after a successful dispatch: a raise above must
                # not leave keys poisoned in the in-flight set forever.
                # Registering entries_by_key lets OTHER prefetchers sharing
                # this cache defer to this wave (cross-replica single flight)
                for k in wave.miss_groups:
                    cache.mark_inflight(k, wave.entries_by_key)
                self.batches += 1
                self.queries += n_valid
        wave.launched_at = self._now()
        for r in reqs:
            r.launched_at = wave.launched_at
        self.launch_seconds += wave.launched_at - t0
        self._waves.append(wave)
        return wave

    # -- collect --------------------------------------------------------------
    @staticmethod
    def _arr_ready(a) -> bool:
        """True once a device array's computation has finished (so forcing
        it would not block).  Non-JAX arrays (numpy, simulator stand-ins
        without the method) are always ready."""
        is_ready = getattr(a, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else True

    def _wave_ready(self, wave: PrefetchWave) -> bool:
        """A wave is collectable without blocking when its retrieval arrays
        have landed AND every deferred request's owner has already collected
        (a deferred entry resolves from the owner's ``entries_by_key``,
        which is empty until then — collecting early would re-dispatch
        nothing but would mis-account the hit).  A wave whose dispatch
        raised, or whose wait has outlived ``retrieval_timeout_s``, is also
        "ready": collecting it runs the retry/failure path instead of
        stalling the scheduler behind a dead or stuck dispatch."""
        for _, k, owner_entries in wave.deferred:
            if owner_entries is not None and k not in owner_entries \
                    and self.cache.is_inflight(k):
                return False
        if not wave.has_misses or wave.launch_error is not None:
            return True
        if self.retrieval_timeout_s is not None and \
                self._now() >= wave.launched_at + self.retrieval_timeout_s:
            return True
        return all(
            self._arr_ready(a)
            for a in (wave.sub.nodes, wave.sub.mask, wave.sub.dist, wave.seeds)
        )

    def ready_index(self) -> Optional[int]:
        """Index of the oldest in-flight wave that can be collected without
        blocking (its device arrays are ready and its deferred owners have
        resolved), or ``None``.  This is the per-request admission hook: a
        continuous scheduler collects whichever wave is done instead of
        stalling on FIFO order behind one slow retrieval row."""
        for i, w in enumerate(self._waves):
            if self._wave_ready(w):
                return i
        return None

    def collect(self, *, step: int = 0, tokens: int = 0,
                sync: bool = False) -> list:
        """Block on the oldest wave and return ``(request, entry, error)``
        triples in arrival order (``entry`` is None exactly when ``error``
        is set — retries exhausted, the engine's degradation ladder takes
        over).  ``sync=True`` marks a launch-then-collect-immediately
        schedule: no overlap is accrued (there was no window to hide in)."""
        wave = self._waves.popleft()
        return self._collect(wave, step=step, tokens=tokens, sync=sync)

    def collect_at(self, index: int, *, step: int = 0,
                   tokens: int = 0) -> list:
        """Collect the wave at ``index`` (from :meth:`ready_index`) out of
        FIFO order.  Safe for any wave — a not-actually-ready wave simply
        blocks — but deferred consistency is only guaranteed for indices
        that :meth:`ready_index` returned (owner waves resolve first)."""
        wave = self._waves[index]
        del self._waves[index]
        return self._collect(wave, step=step, tokens=tokens, sync=False)

    # -- fault containment -----------------------------------------------------
    def _wait_ready(self, arrs, deadline: Optional[float]) -> bool:
        """Poll until every array is ready or ``deadline`` passes.  With no
        deadline, return immediately and let the force block (the original,
        timeout-free behavior)."""
        if deadline is None:
            return True
        while not all(self._arr_ready(a) for a in arrs):
            now = self._now()
            if now >= deadline:
                return False
            self._sleep(min(1e-3, max(deadline - now, 1e-6)))
        return True

    def _force(self, arrs, deadline: Optional[float]) -> tuple:
        """Wait for ``arrs`` (until ``deadline``) and bring them to the
        host: ``(host arrays, None)``, or ``(None, reason)`` on a timeout or
        a raise."""
        with tracing.span("retrieval.wait"):
            if not self._wait_ready(arrs, deadline):
                self.timeouts += 1
                return None, \
                    f"timeout: not ready in {self.retrieval_timeout_s}s"
            try:
                return tuple(np.asarray(a) for a in arrs), None
            except Exception as exc:
                return None, f"force: {exc}"

    def _validate_row(self, nodes, mask) -> Optional[str]:
        """Corrupt-result check: every node id under the valid mask must be
        a real node.  Returns an error reason, or None when clean."""
        if self._n_nodes is None:
            return None
        ids = np.asarray(nodes)[np.asarray(mask, bool)]
        if ids.size and (int(ids.min()) < 0 or int(ids.max()) >= self._n_nodes):
            return (
                f"corrupt: node id out of range [0, {self._n_nodes}) "
                f"(min {int(ids.min())}, max {int(ids.max())})"
            )
        return None

    def _retrieve_once(self, emb) -> tuple:
        """One isolated size-1 dispatch + bounded wait + force + validate.
        Returns ``(entry, None)`` or ``(None, reason)`` — never raises for a
        data-plane fault."""
        t0 = self._now()
        try:
            res = self.pipeline.retrieve_many(
                np.asarray(emb, np.float32)[None], batch_size=1
            )
            sub, seeds, epoch = res.sub, res.seeds, res.epoch
        except Exception as exc:
            return None, f"dispatch: {exc}"
        self.batches += 1
        self.queries += 1
        deadline = None if self.retrieval_timeout_s is None else \
            t0 + self.retrieval_timeout_s
        forced, reason = self._force((sub.nodes, sub.mask, sub.dist, seeds),
                                     deadline)
        if forced is None:
            return None, reason
        nodes, mask, dist, seeds_np = forced
        err = self._validate_row(nodes[0], mask[0])
        if err is not None:
            return None, err
        return CachedRetrieval(
            nodes=nodes[0].copy(), mask=mask[0].copy(),
            dist=dist[0].copy(), seeds=seeds_np[0].copy(), epoch=epoch,
        ), None

    def _retry_group(self, emb, failed_attempts: int,
                     last_reason: str) -> tuple:
        """Relaunch one failed miss-group (size-1 dispatches) until it
        succeeds or the retry budget is spent.  ``failed_attempts`` counts
        dispatches already charged against this group (the batched launch
        counts as one; a deferred orphan adopting a dead owner's key starts
        at zero — its first dispatch is not a retry)."""
        reason = last_reason
        while failed_attempts <= self.max_retries:
            if failed_attempts > 0:
                if self.retry_backoff_s > 0:
                    self._sleep(
                        self.retry_backoff_s * (2 ** (failed_attempts - 1))
                    )
                self.retries += 1
            entry, reason = self._retrieve_once(emb)
            if entry is not None:
                return entry, None
            failed_attempts += 1
        self.failures += 1
        return None, reason

    def _resolve_misses(self, wave: PrefetchWave, entries: dict,
                        failures: dict) -> None:
        """Materialize every miss-group of ``wave`` into ``entries`` (key ->
        CachedRetrieval) or ``failures`` (key -> reason), via the batched
        arrays when they are healthy and the per-group retry path when not."""
        groups = list(wave.miss_groups.items())  # row order == launch order
        todo: dict = {}  # key -> last failure reason (needs retry)
        if wave.launch_error is not None:
            todo = {k: wave.launch_error for k, _ in groups}
        else:
            arrs = (wave.sub.nodes, wave.sub.mask, wave.sub.dist, wave.seeds)
            deadline = None if self.retrieval_timeout_s is None else \
                wave.launched_at + self.retrieval_timeout_s
            forced, reason = self._force(arrs, deadline)
            if forced is None:
                todo = {k: reason for k, _ in groups}
            else:
                nodes, mask, dist, seeds_np = forced
                for row, (k, idxs) in enumerate(groups):
                    err = self._validate_row(nodes[row], mask[row])
                    if err is not None:
                        todo[k] = err
                        continue
                    entries[k] = CachedRetrieval(
                        nodes=nodes[row].copy(), mask=mask[row].copy(),
                        dist=dist[row].copy(), seeds=seeds_np[row].copy(),
                        epoch=wave.epoch,
                    )
        for k, idxs in groups:
            if k not in todo:
                continue
            entry, reason = self._retry_group(
                wave.reqs[idxs[0]].query_emb, 1, todo[k]
            )
            if entry is not None:
                entries[k] = entry
            else:
                failures[k] = reason

    def _collect(self, wave: PrefetchWave, *, step: int, tokens: int,
                 sync: bool) -> list:
        cache = self.cache
        t0 = self._now()
        wave.error_for = [None] * len(wave.reqs)
        if not sync and wave.has_misses:
            # overlap accrues only for waves that actually dispatched a
            # retrieval: a miss-free (all-hit / all-deferred) wave has
            # nothing in flight, so its launch-to-collect window hides
            # nothing and would only inflate the telemetry
            self.waves += 1
            self.overlap_seconds += max(0.0, t0 - wave.launched_at)
            self.overlap_steps += max(0, step - wave.launch_step)
            self.overlap_tokens += max(0, tokens - wave.launch_tokens)
        entries: dict = {}
        failures: dict = {}
        try:
            if wave.has_misses:
                self._resolve_misses(wave, entries, failures)
                self.block_seconds += self._now() - t0

            # deferred first (they are cache *hits* on earlier waves' keys —
            # resolve before this wave's own puts, matching sync get-then-put
            # order), then insert this wave's fresh entries
            for j, k, owner_entries in wave.deferred:
                r = wave.reqs[j]
                e = cache.get(r.query_emb)  # counts the hit, bumps recency
                if e is not None:
                    r.cache_hit = True
                elif owner_entries is not None:
                    # the owner's entry was evicted/expired between its
                    # collect and ours: the get above counted the miss (as
                    # sync would), and instead of re-dispatching we serve the
                    # owner's result — retrieval is deterministic, so the
                    # bits match what sync's re-retrieval would produce — and
                    # re-insert it as that re-retrieval's put would.  Only
                    # the dispatch count diverges from sync here (one fewer,
                    # by design).
                    e = owner_entries.get(k)
                    if e is not None:
                        cache.put(r.query_emb, e)
                if e is None:
                    # orphaned deferral: the owner's group failed (or the
                    # owner was aborted) and its entry never landed — adopt
                    # the key as our own size-1 miss instead of waiting on
                    # a dead wave.  attempts=0: this request never dispatched
                    e, reason = self._retry_group(r.query_emb, 0, "orphaned")
                    if e is not None:
                        cache.put(r.query_emb, e)
                    else:
                        wave.error_for[j] = reason
                wave.entry_for[j] = e
            for row, (k, idxs) in enumerate(wave.miss_groups.items()):
                entry = entries.get(k)
                if entry is None:
                    for j in idxs:
                        wave.error_for[j] = failures.get(k, "unknown fault")
                    continue
                cache.put(wave.reqs[idxs[0]].query_emb, entry)
                wave.entries_by_key[k] = entry
                for j in idxs:
                    wave.entry_for[j] = entry
        finally:
            # even if resolution failed, the keys must leave the in-flight
            # set so later launches re-dispatch instead of deferring to a
            # dead wave — no poisoned keys, ever
            for k in wave.miss_groups:
                cache.release_inflight(k)
            wave.sub = wave.seeds = None  # drop device arrays promptly
        return list(zip(wave.reqs, wave.entry_for, wave.error_for))

    def abort(self) -> list:
        """Discard every in-flight wave: release their in-flight cache keys
        and hand back the never-resolved requests so the engine can mark
        them terminal.  Part of the engine's ``abort()`` reconciliation."""
        orphans = []
        while self._waves:
            w = self._waves.popleft()
            for k in w.miss_groups:
                self.cache.release_inflight(k)
            w.sub = w.seeds = None
            orphans.extend(w.reqs)
        return orphans

    def stats(self) -> dict:
        denom = self.overlap_seconds + self.block_seconds
        return {
            "prefetch_waves": self.waves,
            "overlap_seconds": self.overlap_seconds,
            "overlap_steps": self.overlap_steps,
            "overlap_tokens": self.overlap_tokens,
            "launch_seconds": self.launch_seconds,
            "collect_block_seconds": self.block_seconds,
            "hidden_frac": self.overlap_seconds / denom if denom > 0 else 0.0,
            "retries": self.retries,
            "timeouts": self.timeouts,
            "retrieval_failures": self.failures,
        }

    def stats_ns(self) -> dict:
        """Namespaced stats (unified serving schema): the prefetcher's
        counters under ``prefetch.*`` — see :mod:`repro.serving.stats`."""
        return {"prefetch": self.stats()}
