"""One step of dispatch lookahead on the contiguous one-token decode path:
every request's tokens, ``truncated`` flag and the step count equal those of
the same engine stepped synchronously, and ``ahead_steps`` counts exactly the
steps dispatched before the previous step was read."""
import jax
import numpy as np
import pytest

from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import Request, ServeEngine

CFG = TransformerConfig(
    name="ahead-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
    d_head=16, d_ff=64, vocab=64, dtype="float32",
)
PARAMS = tm.init_params(jax.random.PRNGKey(0), CFG)
SLOTS = 4
CACHE_LEN = 32


class SyncEngine(ServeEngine):
    """The same engine with lookahead held off: every step is read before
    the next one is dispatched."""

    def _may_look_ahead(self) -> bool:
        return False


class WatchedEngine(ServeEngine):
    """Records, per ``step`` call, whether a step was already on the chip
    when admission ran and when requests finished."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.admitted_in_flight = 0  # requests admitted behind a queued step
        self.retired_in_flight = []  # finished while the next step was queued

    def _admit(self):
        queued, in_flight = len(self.queue), self._ahead is not None
        out = super()._admit()
        if in_flight:
            self.admitted_in_flight += queued - len(self.queue)
        return out

    def step(self):
        out = super().step()
        if self._ahead is not None:
            self.retired_in_flight.extend(out)
        return out


def _requests(budgets, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(uid=u, prompt_ids=rng.integers(
                1, 64, size=int(rng.integers(3, 9))).astype(np.int32),
                    max_new_tokens=mn)
            for u, mn in enumerate(budgets)]


# budgets mixed, one past the arena (truncated), more requests than slots
MIXED = [5, 9, 40, 7, 12, 3, 20, 6]
# every slot runs into the arena's end, at a cursor set by its prompt
ARENA = [40, 40, 40, 40, 9, 5]


def _run(cls, budgets=MIXED, eos_id=None, slots=SLOTS, **kw):
    eng = cls(PARAMS, CFG, slots=slots, cache_len=CACHE_LEN, eos_id=eos_id,
              **kw)
    for r in _requests(budgets):
        eng.submit(r)
    done = {r.uid: r for r in eng.run_to_completion()}
    return eng, done


def _assert_same(eng, done, ref_eng, ref):
    assert set(done) == set(ref)
    for u in ref:
        assert done[u].out_tokens == ref[u].out_tokens, f"uid {u}"
        assert done[u].truncated == ref[u].truncated, f"uid {u}"
    assert eng.decode_steps == ref_eng.decode_steps
    assert eng.decode_tokens == ref_eng.decode_tokens
    assert eng.truncations == ref_eng.truncations
    assert eng._ahead is None  # run_to_completion leaves nothing queued


def _mid_stream_eos(**kw):
    """A token the synchronous run emits mid-stream (never as a request's
    first token), so an EOS on it retires a slot between two decode
    steps."""
    _, ref = _run(SyncEngine, **kw)
    firsts = {r.out_tokens[0] for r in ref.values()}
    for r in ref.values():
        for t in r.out_tokens[3:]:
            if t not in firsts:
                return t
    pytest.fail("no mid-stream token to stop on")


@pytest.mark.parametrize("budgets", [MIXED, ARENA], ids=["mixed", "arena"])
def test_full_slots_mixed_budgets_match_sync(budgets):
    ref_eng, ref = _run(SyncEngine, budgets)
    eng, done = _run(ServeEngine, budgets)
    _assert_same(eng, done, ref_eng, ref)
    assert any(r.truncated for r in done.values())  # the arena-full retire
    assert 0 < eng.ahead_steps < eng.decode_steps
    assert ref_eng.ahead_steps == 0


def test_eos_while_next_step_is_queued_then_admission_into_its_slot():
    eos = _mid_stream_eos()
    ref_eng, ref = _run(SyncEngine, eos_id=eos)
    eng, done = _run(WatchedEngine, eos_id=eos)
    _assert_same(eng, done, ref_eng, ref)
    stopped = [r for r in eng.retired_in_flight
               if r.out_tokens[-1] == eos
               and len(r.out_tokens) < r.max_new_tokens]
    assert stopped, "no EOS fired while the next step was on the chip"
    assert eng.admitted_in_flight > 0  # the freed slot was refilled


def test_eos_on_the_only_live_slot_drops_the_queued_step():
    """One slot: an EOS retires every request the queued step serves, so
    that step is dropped and the next request is admitted with nothing on
    the chip, as in a synchronous run."""
    budgets = [14, 14, 14]
    eos = _mid_stream_eos(budgets=budgets, slots=1)
    ref_eng, ref = _run(SyncEngine, budgets, eos_id=eos, slots=1)
    eng, done = _run(WatchedEngine, budgets, eos_id=eos, slots=1)
    _assert_same(eng, done, ref_eng, ref)
    assert any(len(r.out_tokens) < r.max_new_tokens for r in done.values())
    assert eng.retired_in_flight == [] and eng.admitted_in_flight == 0


@pytest.mark.parametrize("steps", [1, 3, 5])
def test_abort_while_a_step_is_queued(steps):
    runs = {}
    for cls in (SyncEngine, ServeEngine):
        eng = cls(PARAMS, CFG, slots=SLOTS, cache_len=CACHE_LEN)
        for r in _requests(MIXED):
            eng.submit(r)
        for _ in range(steps):
            eng.step()
        if cls is ServeEngine:
            assert eng._ahead is not None
        aborted = {r.uid: r for r in eng.abort("stop")}
        assert eng._ahead is None and not eng.live.any()
        assert eng.decode_stats()["decode_steps"] == steps
        # reusable afterwards: a fresh workload on the aborted arena
        for r in _requests([6, 11, 4, 9], seed=9):
            eng.submit(r)
        runs[cls] = (aborted, {r.uid: r for r in eng.run_to_completion()},
                     eng.decode_steps)
    (ab0, done0, n0), (ab1, done1, n1) = runs[SyncEngine], runs[ServeEngine]
    assert set(ab0) == set(ab1)
    for u in ab0:
        assert ab1[u].out_tokens == ab0[u].out_tokens
        assert ab1[u].failed and ab1[u].error == "stop"
    assert {u: r.out_tokens for u, r in done1.items()} == \
        {u: r.out_tokens for u, r in done0.items()}
    assert n1 == n0


def test_ahead_share_counts_steps_dispatched_before_the_last_read():
    # two waves of 8 full slots, 16-token budgets: 15 decode steps a wave,
    # the first read with nothing queued, and no lookahead past the last
    eng = ServeEngine(PARAMS, CFG, slots=8, cache_len=CACHE_LEN)
    for r in _requests([16] * 16):
        eng.submit(r)
    eng.run_to_completion()
    assert eng.decode_steps == 30
    assert eng.ahead_steps == 28
    ds = eng.stats_ns()["decode"]
    assert ds["ahead_steps"] == 28
    assert ds["ahead_share"] == pytest.approx(28 / 30)


def test_free_slot_never_looks_ahead():
    eng, _ = _run(ServeEngine, budgets=[16] * 7, slots=8)
    assert eng.decode_steps == 15
    assert eng.ahead_steps == 0
    assert eng.decode_stats()["ahead_share"] == 0.0


@pytest.mark.parametrize("kw", [{"paged_kv": True}, {"spec_decode": True},
                                {"paged_kv": True, "spec_decode": True}],
                         ids=["paged", "spec", "paged-spec"])
def test_paged_and_speculative_paths_read_every_step(kw):
    ref_eng, ref = _run(ServeEngine, budgets=[16] * 8, slots=8)
    eng, done = _run(ServeEngine, budgets=[16] * 8, slots=8, **kw)
    assert eng.ahead_steps == 0
    assert {u: r.out_tokens for u, r in done.items()} == \
        {u: r.out_tokens for u, r in ref.items()}
