"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler ships with libtpu and compiles for a topology that is only
described, so these tests catch what interpret-mode kernel tests cannot —
block shapes the TPU lowering refuses, programs that do not fit the chip's
16 GiB — at no chip time.  Nothing runs: a passing compile is not a chip
run.  The topology is described inside a module fixture (never at import),
and every compile lives in this one file so one worker owns libtpu.
"""
import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.kernels.bfs_frontier.kernel import frontier_hop_kernel
from repro.kernels.frontier_expand.kernel import ws_mark_kernel
from repro.kernels.topk_sim import ops as topk_ops
from repro.models.transformer import model as tm
from repro.serving.engine import _prefill_batch

HBM_BYTES = 16 * 2**30  # one v5e chip
ARXIV_NODES = 169_343


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _compile(fn, *args):
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, used
    return used


@pytest.mark.parametrize("n", [ARXIV_NODES, 612_258])  # arxiv; products/4
def test_topk_sim_compiles(sds, monkeypatch, n):
    # the op picks its interpreter off-TPU; compile the kernel itself
    monkeypatch.setattr(topk_ops, "_on_tpu", lambda: True)
    op = functools.partial(topk_ops.topk_similarity.__wrapped__, k=8,
                           use_kernel=True)
    compiled = _compile(op, sds((128, 128), jnp.float32),
                        sds((n, 128), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.fixture(scope="module")
def lm(sds):
    cfg = C.get_config("starcoder2-3b").model_cfg  # published widths
    shapes = jax.eval_shape(lambda k: tm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), shapes)
    return cfg, params


def test_serve_step_compiles_at_published_width(lm, sds):
    cfg, params = lm
    cache = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                         jax.eval_shape(lambda: tm.init_cache(cfg, 4, 4096)))
    compiled = _compile(functools.partial(tm.serve_step, cfg=cfg), params,
                        cache, sds((4,), jnp.int32))
    assert _fits(compiled) > 8 * 2**30  # the bf16 weights are all there


def test_prefill_compiles_at_published_width(lm, sds):
    cfg, params = lm
    compiled = _compile(
        functools.partial(_prefill_batch, cfg=cfg, cache_len=4096), params,
        sds((4, 512), jnp.int32), sds((4,), jnp.int32))
    assert _fits(compiled) > 8 * 2**30


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "TPU lowering refuses ws_mark: its (1, C) workset block breaks the "
    "(8, 128) block rule (and its in-VMEM 1-D gather is unsupported); the "
    "op defaults to its jnp arm"))
def test_ws_mark_kernel_compiles(sds):
    op = functools.partial(ws_mark_kernel, blk_w=1024, interpret=False)
    _compile(op, sds((4, 2048), jnp.int32), sds((4, 2048 * 1024), jnp.int32))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "TPU lowering refuses bfs_frontier: its (1, N+1) frontier block breaks "
    "the (8, 128) block rule (and its in-VMEM 1-D gather is unsupported); "
    "the op defaults to its jnp arm"))
def test_bfs_frontier_kernel_compiles(sds):
    n = 1024 * 165
    op = functools.partial(frontier_hop_kernel, blk_n=512, interpret=False)
    _compile(op, sds((4, n + 1), jnp.int8), sds((n, 16), jnp.int32),
             sds((n, 16), jnp.bool_))
