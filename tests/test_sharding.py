"""ShardedIndex: parity with the unsharded brute scan (same ids, scores
within the stated tolerance), the hierarchical merge oracle, and a forced
multi-device CPU mesh subprocess."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BruteIndex, ShardedIndex, hierarchical_topk_merge
from repro.core.indexing import build_index
from repro.core.sharding import SCORE_ATOL, SCORE_RTOL


def _assert_same_topk(brute, sharded) -> None:
    """The sharded contract: scores within (SCORE_RTOL, SCORE_ATOL) of the
    unsharded scan's, and the same id at every rank whose score is not
    within that tolerance of a neighbouring rank's (near-ties may swap)."""
    bs, bi = (np.asarray(a) for a in brute)
    ss, si = (np.asarray(a) for a in sharded)
    assert bs.shape == ss.shape and bi.shape == si.shape
    np.testing.assert_allclose(ss, bs, rtol=SCORE_RTOL, atol=SCORE_ATOL)
    tol = SCORE_ATOL + SCORE_RTOL * np.abs(bs)
    gap = np.abs(np.diff(bs, axis=1))
    tied = np.zeros(bs.shape, bool)
    tied[:, 1:] |= gap <= tol[:, 1:]
    tied[:, :-1] |= gap <= tol[:, :-1]
    np.testing.assert_array_equal(bi[~tied], si[~tied])


# ---------------------------------------------------------------- merge ----
def test_hierarchical_merge_matches_flat_topk(rng):
    for s, q, w, k in [(2, 3, 5, 4), (5, 2, 7, 9), (8, 4, 3, 6), (1, 2, 6, 3)]:
        scores = jnp.asarray(rng.standard_normal((s, q, w)), jnp.float32)
        ids = jnp.asarray(rng.permutation(s * q * w)[: s * q * w]
                          .reshape(s, q, w), jnp.int32)
        ms, mi = hierarchical_topk_merge(scores, ids, k)
        flat_s = np.asarray(scores.transpose(1, 0, 2).reshape(q, -1))
        flat_i = np.asarray(ids.transpose(1, 0, 2).reshape(q, -1))
        kk = min(k, s * w)
        for qi in range(q):
            order = np.lexsort((flat_i[qi], -flat_s[qi]))[:kk]
            np.testing.assert_array_equal(np.asarray(mi)[qi],
                                          flat_i[qi][order])
            np.testing.assert_array_equal(np.asarray(ms)[qi],
                                          flat_s[qi][order])


def test_hierarchical_merge_breaks_ties_by_id(rng):
    # identical scores everywhere -> merge must return the lowest ids
    s, q, w, k = 4, 2, 3, 5
    scores = jnp.ones((s, q, w), jnp.float32)
    perm = np.tile(rng.permutation(s * w), (q, 1))  # same ids for each query
    ids = jnp.asarray(perm.reshape(q, s, w).transpose(1, 0, 2), jnp.int32)
    _, mi = hierarchical_topk_merge(scores, ids, k)
    np.testing.assert_array_equal(np.asarray(mi),
                                  np.tile(np.arange(k), (q, 1)))


# ------------------------------------------------- logical-shard parity ----
@pytest.mark.parametrize("n,n_shards,k", [
    (101, 3, 7),     # N not divisible by shard count
    (96, 4, 5),      # divisible
    (60, 7, 60),     # k == N, shards uneven
    (2500, 2, 11),   # big enough for the kernel path on the unsharded side
])
def test_sharded_matches_brute_bitwise(rng, n, n_shards, k):
    emb = rng.standard_normal((n, 32)).astype(np.float32)
    q = rng.standard_normal((5, 32)).astype(np.float32)
    brute = BruteIndex.build(emb).search(q, k)
    _assert_same_topk(
        brute, ShardedIndex.build(emb, n_shards=n_shards).search(q, k))


def test_sharded_tie_breaking_with_duplicate_rows(rng):
    # duplicated rows across shard boundaries -> score ties; every tied
    # group must hold the same ids, and untied ranks the same id
    base = rng.standard_normal((40, 16)).astype(np.float32)
    emb = np.concatenate([base, base, base])  # ids i, i+40, i+80 tie
    q = base[:4] + 0.0
    brute = BruteIndex.build(emb).search(q, 9)
    sharded = ShardedIndex.build(emb, n_shards=5).search(q, 9)
    _assert_same_topk(brute, sharded)
    # the 9 hits are 3 tie groups of 3 duplicates: same id sets per group
    bi, si = np.asarray(brute[1]), np.asarray(sharded[1])
    for g in range(3):
        cols = slice(3 * g, 3 * g + 3)
        np.testing.assert_array_equal(np.sort(bi[:, cols]),
                                      np.sort(si[:, cols]))
        np.testing.assert_array_equal(np.sort(si[:, cols]) % 40,
                                      si[:, cols] % 40)


def test_sharded_single_shard_and_build_index_kinds(rng):
    emb = rng.standard_normal((50, 8)).astype(np.float32)
    q = rng.standard_normal((3, 8)).astype(np.float32)
    brute = BruteIndex.build(emb).search(q, 4)
    one = build_index(emb, kind="sharded", n_shards=1)
    _assert_same_topk(brute, one.search(q, 4))
    sivf = build_index(emb, kind="sharded_ivf", n_shards=2, n_clusters=4,
                       nprobe=4)
    s2, i2 = sivf.search(q, 4)
    assert s2.shape == (3, 4) and int(np.asarray(i2).max()) < 50


def test_sharded_empty_trailing_shard(rng):
    """ceil-partitioning can leave a shard with zero real rows (n=5, s=4 ->
    rows_per_shard=2 and shard 3 is all padding); both inners must cope."""
    emb = rng.standard_normal((5, 8)).astype(np.float32)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    brute = BruteIndex.build(emb).search(q, 5)
    _assert_same_topk(brute, ShardedIndex.build(emb, n_shards=4).search(q, 5))
    sv = ShardedIndex.build(emb, n_shards=4, inner="ivf", n_clusters=4,
                            nprobe=4)
    s2, i2 = sv.search(q, 3)
    assert int(np.asarray(i2).max()) < 5
    assert np.isfinite(np.asarray(s2)).all()


def test_sharded_ivf_recall_vs_brute(rng):
    emb = rng.standard_normal((1200, 32)).astype(np.float32)
    q = rng.standard_normal((12, 32)).astype(np.float32)
    _, bi = BruteIndex.build(emb).search(q, 10)
    sivf = ShardedIndex.build(emb, n_shards=3, inner="ivf", n_clusters=8,
                              nprobe=8)  # nprobe == C: exhaustive per shard
    _, si = sivf.search(q, 10)
    rec = np.mean([
        len(set(np.asarray(si[r]).tolist())
            & set(np.asarray(bi[r]).tolist())) / 10
        for r in range(12)
    ])
    assert rec >= 0.99, rec  # all lists probed in every shard -> exact


# ----------------------------------------------- forced multi-device mesh ----
_PARITY_SCRIPT = """
import numpy as np, jax
from repro.core import BruteIndex, ShardedIndex
from repro.core.sharding import SCORE_ATOL, SCORE_RTOL

assert jax.device_count() == 4, jax.device_count()
rng = np.random.default_rng(7)
for n, s, k in [(2898, 4, 9), (3001, 4, 17), (4096, 8, 5)]:
    emb = rng.standard_normal((n, 48)).astype(np.float32)
    q = rng.standard_normal((6, 48)).astype(np.float32)
    bs, bi = BruteIndex.build(emb).search(q, k)
    idx = ShardedIndex.build(emb, n_shards=s)
    assert idx.mesh.size == 4, idx.mesh.size  # a real 4-way mesh
    # every device holds only its own shards between searches
    held = {sh.device: sh.data.shape[0] for sh in
            idx.emb_shards.addressable_shards}
    assert len(held) == 4 and set(held.values()) == {s // 4}, held
    ss, si = idx.search(q, k)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(bs),
                               rtol=SCORE_RTOL, atol=SCORE_ATOL)
    assert np.array_equal(np.asarray(bi), np.asarray(si)), (n, s, "ids")
print("MESH_PARITY_OK")
"""


def test_sharded_parity_on_forced_multidevice_mesh():
    """Same ids and scores within tolerance on a real >= 4-way CPU mesh,
    including N not divisible by the shard count, with each device holding
    only its own shards.  Runs in a subprocess because
    --xla_force_host_platform_device_count must be set before jax init."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=4", ""
        )
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_PARITY_OK" in out.stdout
