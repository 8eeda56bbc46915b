"""Latent attention (MLA) and the held-share MoE in the program: the
absorbed decode against the expanded form, YaRN against the published
formulas, attention with a narrower value and its own scale, the paths
that refuse a latent cache, and the registry's capacity-capped MoE
configs kept bit for bit."""
import hashlib
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import model as tm
from repro.models.transformer.attention import (
    chunked_attention, dense_attention, yarn_inv_freq,
)
from repro.models.transformer.config import (
    MLAConfig, MoEConfig, TransformerConfig,
)

PINS = Path(__file__).resolve().parent / "fixtures" / "pinned-moe.json"
YARN = dict(yarn_factor=40.0, yarn_original_max=4096, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707)
MLA = MLAConfig(kv_rank=32, rope_dim=8, nope_dim=16, v_dim=16, **YARN)
CFG = TransformerConfig(name="tiny-mla", n_layers=2, d_model=64, n_heads=4,
                        n_kv_heads=4, d_head=16, d_ff=128, vocab=97,
                        dtype="float32", mla=MLA)


# -- YaRN, transcribed from the published DeepSeek-V2 code in NumPy ---------
def _np_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def _np_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


@pytest.mark.parametrize("dim,factor", [(64, 40.0), (8, 40.0), (64, 1.0)])
def test_yarn_matches_the_published_formulas(dim, factor):
    got = yarn_inv_freq(dim, 10000.0, factor, 4096, 32.0, 1.0)
    want = (_np_yarn(dim, 10000.0, factor, 4096, 32, 1) if factor > 1
            else 1.0 / 10000.0 ** (np.arange(0, dim, 2) / dim))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_yarn_scales_of_deepseek_v2_lite():
    m = MLAConfig(kv_rank=512, rope_dim=64, nope_dim=128, v_dim=128, **YARN)
    want = 192 ** -0.5 * _np_mscale(40, 0.707) ** 2
    assert m.softmax_scale == pytest.approx(want, rel=1e-12)
    assert m.softmax_scale == pytest.approx(0.11472, abs=5e-6)
    assert m.rope_mscale == 1.0
    # YaRN moves the frequencies at every length: the slow dims interpolate
    f = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    assert f[0] == plain[0] and f[-1] == pytest.approx(plain[-1] / 40)


# -- attention with a narrower value and its own scale ---------------------
@pytest.mark.parametrize("window", [None, 24])
def test_chunked_attention_with_narrow_values_equals_dense(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 24))
    k = jax.random.normal(ks[1], (2, 64, 4, 24))
    v = jax.random.normal(ks[2], (2, 64, 4, 8))
    f1 = lambda *a: chunked_attention(*a, window=window, q_chunk=16,
                                      kv_chunk=16, scale=0.3)
    f2 = lambda *a: dense_attention(*a, window=window, scale=0.3)
    np.testing.assert_allclose(np.asarray(f1(q, k, v)),
                               np.asarray(f2(q, k, v)), atol=2e-5)
    g1 = jax.grad(lambda *a: f1(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: f2(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# -- the absorbed decode against the expanded form at the same cache -------
@pytest.mark.parametrize("window", [None, 5])
def test_absorbed_decode_equals_expanded_attention(window):
    cfg = CFG if window is None else TransformerConfig(
        **{**CFG.__dict__, "sliding_window": window})
    m = cfg.mla
    b, sc, h = 3, 12, cfg.n_heads
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q_nope = jax.random.normal(ks[0], (b, h, m.nope_dim))
    q_pe = jax.random.normal(ks[1], (b, h, m.rope_dim))
    c = jax.random.normal(ks[2], (b, sc, m.kv_rank))
    pe = jax.random.normal(ks[3], (b, sc, m.rope_dim))
    p = {"wkv_b": jax.random.normal(ks[4], (m.kv_rank, h * (m.nope_dim
                                                            + m.v_dim))) * 0.2}
    cur = jnp.asarray([11, 4, 7], jnp.int32)
    pos = jnp.where(jnp.arange(sc)[None] <= cur[:, None], jnp.arange(sc), -1)
    got = tm._mla_absorbed(p, q_nope, q_pe, c, pe, pos, cur, cfg)
    # expanded: every cached latent through kv_b into per-head keys/values
    kv = (c @ p["wkv_b"]).reshape(b, sc, h, m.nope_dim + m.v_dim)
    k = jnp.concatenate([kv[..., :m.nope_dim], jnp.broadcast_to(
        pe[:, :, None], (b, sc, h, m.rope_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("bhd,bshd->bhs", q, k) * m.softmax_scale
    ok = (pos >= 0) & (pos <= cur[:, None])
    if window is not None:
        ok &= cur[:, None] - pos < window
    pr = jax.nn.softmax(jnp.where(ok[:, None], s, -jnp.inf), -1)
    want = jnp.einsum("bhs,bshd->bhd", pr, kv[..., m.nope_dim:])
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(b, -1), atol=1e-5)


# -- the paths that do not carry a latent cache refuse it -------------------
@pytest.mark.parametrize("kw", [{"paged_kv": True}, {"spec_decode": True}],
                         ids=["paged", "spec"])
def test_paged_arena_and_spec_decode_refuse_latent_attention(kw):
    from repro.serving import ServeEngine

    with pytest.raises(ValueError, match="latent attention"):
        ServeEngine(None, CFG, slots=2, cache_len=32, **kw)
    with pytest.raises(ValueError, match="latent"):
        tm.init_paged_cache(CFG, 2, 32, 8, 8)


def test_leading_dense_layers_need_the_latent_path():
    with pytest.raises(ValueError, match="dense layers"):
        TransformerConfig(name="x", n_layers=2, d_model=8, n_heads=2,
                          n_kv_heads=2, d_head=4, d_ff=8, vocab=8,
                          moe=MoEConfig(n_experts=4, top_k=2, d_ff=8,
                                        capacity_factor=None,
                                        dense_layers=1))


# -- the capacity-capped MoE configs keep their outputs bit for bit --------
def _h(x):
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "grok-1-314b"])
def test_capacity_moe_configs_keep_their_outputs(arch):
    """Hashes of the forward, loss, prefill and decode logits of the
    registry's reduced MoE configs, read before the held-share layer and
    latent attention were added."""
    pin = json.loads(PINS.read_text())[arch]
    cfg = get_config(arch).reduced_cfg
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 128, (2, 24)),
                       jnp.int32)
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    assert _h(tm.lm_logits(params, toks, cfg)) == pin["lm_logits"]
    loss, _ = tm.lm_loss(params, toks, jnp.ones_like(toks), cfg)
    assert _h(loss) == pin["lm_loss"]
    logits, cache = tm.prefill(params, toks[:, :16],
                               jnp.asarray([16, 11], jnp.int32), cfg, 32)
    assert _h(logits) == pin["prefill"]
    step = jax.jit(tm.decode_step, static_argnames=("cfg",))
    dec = []
    for t in range(16, 20):
        logits, cache = step(params, cache, toks[:, t], cfg)
        dec.append(np.asarray(logits))
    assert _h(np.stack(dec)) == pin["decode"]
