"""LM stack: attention equivalence, flash VJP, serve-path consistency, MoE."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.transformer import MoEConfig, TransformerConfig, model as tm
from repro.models.transformer import attention as attn
from repro.models.transformer.attention import chunked_attention, dense_attention
from repro.models.transformer.moe import init_moe_params, moe_ffn
from repro.serving.engine import _merge_admitted

CFG = TransformerConfig(
    name="tiny", n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
    d_ff=128, vocab=97, dtype="float32",
)


def _qkv(s=256, h=8, kv=2, dh=32, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (b, s, h, dh)),
        jax.random.normal(ks[1], (b, s, kv, dh)),
        jax.random.normal(ks[2], (b, s, kv, dh)),
    )


@pytest.mark.parametrize("window", [None, 64])
def test_chunked_equals_dense(window):
    q, k, v = _qkv()
    o1 = chunked_attention(q, k, v, window=window, q_chunk=64, kv_chunk=64)
    o2 = dense_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.parametrize("window", [None, 48])
def test_flash_vjp_matches_autodiff(window):
    q, k, v = _qkv(s=128, h=4, kv=2, dh=16)
    f1 = lambda *a: chunked_attention(*a, window=window, q_chunk=32, kv_chunk=32).sum()
    f2 = lambda *a: dense_attention(*a, window=window).sum()
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_lm_loss_near_uniform_at_init():
    params = tm.init_params(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, CFG.vocab)
    loss, _ = tm.lm_loss(params, toks, jnp.ones((2, 32), bool), CFG)
    assert abs(float(loss) - np.log(CFG.vocab)) < 1.5


def test_loss_chunking_invariance():
    params = tm.init_params(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, CFG.vocab)
    mask = jnp.ones((2, 33), bool)
    import dataclasses

    l1, _ = tm.lm_loss(params, toks, mask, dataclasses.replace(CFG, loss_chunk=8))
    l2, _ = tm.lm_loss(params, toks, mask, dataclasses.replace(CFG, loss_chunk=32))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_prefill_decode_match_teacher_forcing():
    params = tm.init_params(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, CFG.vocab)
    full = tm.lm_logits(params, toks, CFG)
    logits, cache = tm.prefill(params, toks[:, :16], jnp.array([16, 16]), CFG, 24)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, 15]), rtol=3e-4, atol=3e-4
    )
    for t in range(16, 24):
        logits, cache = tm.decode_step(params, cache, toks[:, t], CFG)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, t]), rtol=5e-4, atol=5e-4
        )


def test_ring_buffer_sliding_window_decode():
    import dataclasses

    cfgw = dataclasses.replace(CFG, sliding_window=8, n_layers=2)
    params = tm.init_params(jax.random.PRNGKey(1), cfgw)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, cfgw.vocab)
    full = tm.lm_logits(params, toks, cfgw)
    lg, cache = tm.prefill(params, toks[:, :8], jnp.array([8, 8]), cfgw, 8)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, 7]), atol=1e-3)
    for t in range(8, 24):  # decode far past the cache length
        lg, cache = tm.decode_step(params, cache, toks[:, t], cfgw)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full[:, t]), rtol=2e-3, atol=2e-3
        )


def test_variable_length_prefill():
    params = tm.init_params(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, CFG.vocab)
    # row 1 has true length 10: its prefill logits must match a 10-token run
    logits, _ = tm.prefill(params, toks, jnp.array([16, 10]), CFG, 16)
    short = tm.lm_logits(params, toks[1:2, :10], CFG)
    np.testing.assert_allclose(
        np.asarray(logits[1]), np.asarray(short[0, 9]), rtol=3e-4, atol=3e-4
    )


# ------------------------------------------------------------------- MoE ---
def test_moe_capacity_and_combine():
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=32, capacity_factor=8.0)
    p = init_moe_params(jax.random.PRNGKey(0), 16, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    y, aux = moe_ffn(p, x, cfg)
    assert y.shape == x.shape and float(aux) > 0
    # with huge capacity nothing is dropped: compare to dense per-expert eval
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gate, top_e = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    expect = np.zeros_like(np.asarray(x))
    for t in range(24):
        for j in range(2):
            e = int(top_e[t, j])
            h = jax.nn.silu(x[t] @ p["w1"][e]) * (x[t] @ p["w3"][e])
            expect[t] += float(gate[t, j]) * np.asarray(h @ p["w2"][e])
    np.testing.assert_allclose(np.asarray(y), expect, rtol=2e-4, atol=2e-4)


def test_moe_drops_overflow_at_low_capacity():
    cfg = MoEConfig(n_experts=2, top_k=1, d_ff=8, capacity_factor=0.25)
    p = init_moe_params(jax.random.PRNGKey(0), 8, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    y, _ = moe_ffn(p, x, cfg)
    # some tokens must be zeroed (dropped)
    dropped = np.asarray(jnp.all(y == 0, axis=-1)).sum()
    assert dropped > 0


def test_moe_lm_trains():
    cfg = TransformerConfig(
        name="m", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
        d_ff=0, vocab=64, dtype="float32",
        moe=MoEConfig(n_experts=4, top_k=2, d_ff=64),
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    data = np.tile(np.random.default_rng(0).integers(0, 64, (4, 8)), (1, 4))
    toks = jnp.asarray(data, jnp.int32)
    mask = jnp.ones_like(toks, bool)

    def loss(p):
        return tm.lm_loss(p, toks, mask, cfg)[0]

    g = jax.grad(loss)(params)
    l0 = float(loss(params))
    p2 = jax.tree.map(lambda a, b: a - 0.5 * b, params, g)
    assert float(loss(p2)) < l0


def test_int8_kv_cache_decode_accuracy():
    """int8 KV cache (kv_quant): decode logits match fp32 within quant noise."""
    import dataclasses

    cfgq = dataclasses.replace(CFG, kv_quant=True)
    params = tm.init_params(jax.random.PRNGKey(0), cfgq)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, CFG.vocab)
    full = tm.lm_logits(params, toks, CFG)
    logits, cache = tm.prefill(params, toks[:, :16], jnp.array([16, 16]), cfgq, 24)
    assert cache.k.dtype == jnp.int8 and cache.k_scale is not None
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, 15]),
                               atol=5e-3)
    errs = []
    for t in range(16, 24):
        logits, cache = tm.decode_step(params, cache, toks[:, t], cfgq)
        errs.append(float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert max(errs) < 0.05, errs


# ------------------------------------------------- in-place decode step ---
def _masked_step(params, cache, token, cfg):
    """The decode step with the arena update it replaced: each layer's new
    row written by a select over every row of the layer, the arena passed
    through the layer loop and stacked again."""
    b, sc, cur = token.shape[0], cache.k.shape[2], cache.cursor
    mask = jnp.arange(sc)[None, :] == (cur % sc)[:, None]
    pos = jnp.where(mask, cur[:, None], cache.pos)
    x = params["embed"][token][:, None]

    def put(rows, new):  # rows (B, Sc, ...) <- new (B, ...) at each slot
        return jnp.where(mask.reshape(mask.shape + (1,) * (rows.ndim - 2)),
                         new[:, None], rows)

    def llama(x, xs):
        p, kc, vc, ks, vs = xs
        xn = tm.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = tm._attn_proj(p, xn, cfg)
        q = attn.rope(q, cur[:, None], cfg.rope_theta)
        k = attn.rope(k, cur[:, None], cfg.rope_theta)
        if cfg.kv_quant:
            k, ksc = tm._quant_rows(k)
            v, vsc = tm._quant_rows(v)
            ks, vs = put(ks, ksc[:, 0]), put(vs, vsc[:, 0])
        kc, vc = put(kc, k[:, 0]), put(vc, v[:, 0])
        o = attn.decode_attention(q, kc, vc, pos, cur, cfg.sliding_window,
                                  k_scale=ks, v_scale=vs)
        x = x + (o.reshape(b, 1, -1) @ p["wo"]).astype(x.dtype)
        xn = tm.rms_norm(x, p["ln2"], cfg.norm_eps)
        y = (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"]
        return x + y.astype(x.dtype), (kc, vc, ks, vs)

    def mla(carry, xs, dense):
        x, routed, top = carry
        p, kc, pc = xs
        xn = tm.rms_norm(x, p["ln1"], cfg.norm_eps)
        q_nope, q_pe, c, k_pe = tm._mla_proj(p, xn, cur[:, None], cfg)
        kc, pc = put(kc, c[:, 0]), put(pc, k_pe[:, 0])
        o = tm._mla_absorbed(p, q_nope[:, 0], q_pe[:, 0], kc, pc, pos, cur,
                             cfg)
        x = x + (o[:, None] @ p["wo"]).astype(x.dtype)
        xn = tm.rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _, load = tm._mla_ffn(p, xn.reshape(b, -1), cfg, dense)
        if load is not None:
            routed, top = routed + load, jnp.maximum(top, jnp.max(load))
        return (x + y[:, None].astype(x.dtype), routed, top), (kc, pc)

    counters = {}
    if cfg.mla is None:
        x, (kc, vc, ks, vs) = jax.lax.scan(
            llama, x, (params["layers"], cache.k, cache.v, cache.k_scale,
                       cache.v_scale))
    else:
        ks = vs = None
        carry = (x, cache.routed, jnp.zeros((), jnp.int32))
        kcs, pcs, first = [], [], 0
        for stack, dense in tm._mla_segments(params, cfg):
            n = jax.tree.leaves(stack)[0].shape[0]
            carry, (kc, pc) = jax.lax.scan(
                lambda c, xs, dense=dense: mla(c, xs, dense), carry,
                (stack, cache.k[first:first + n], cache.v[first:first + n]))
            kcs.append(kc)
            pcs.append(pc)
            first += n
        x, routed, top = carry
        kc, vc = jnp.concatenate(kcs), jnp.concatenate(pcs)
        counters = {"routed": routed,
                    "max_load": jnp.maximum(cache.max_load, top)}
    x = tm.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, 0].astype(jnp.float32) @ params["head"].astype(jnp.float32)
    return jnp.argmax(logits, -1).astype(jnp.int32), tm.KVCache(
        k=kc, v=vc, pos=pos, cursor=cur + 1, k_scale=ks, v_scale=vs,
        **counters)


def _step_case(kind: str):
    """(cfg, params) at bf16: the Llama family here, or the tiny
    DeepSeek-V2 block of the benchmark's fixtures (latent cache, a leading
    dense layer, held-share MoE with its routing counters)."""
    if kind != "mla":
        cfg = dataclasses.replace(CFG, dtype="bfloat16",
                                  kv_quant=kind == "kv_quant")
        return cfg, tm.init_params(jax.random.PRNGKey(0), cfg)
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import models

    model = json.loads((root / "tests" / "bench" / "fixtures" /
                        "tiny-DeepseekV2ForCausalLM.json").read_text())["model"]
    model = dict(model, torch_dtype="bfloat16")
    fam = models.family(model)
    return fam.program_config(model, "tiny"), fam.make_params(model, 0)


@pytest.mark.parametrize("kind", ["bf16", "kv_quant", "mla"])
def test_in_place_decode_step_equals_masked_update(kind):
    """``serve_step`` writes each slot's new row into the donated arena;
    over 12 steps it emits the tokens and leaves the arena, positions and
    cursors bit for bit as the masked update over every row did.  Slots
    start at cursors 12, 5, 9 and empty; slot 3 is admitted after step 3,
    and slots 0, 1 and 2 wrap past the 16-row arena."""
    cfg, params = _step_case(kind)
    prompts = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, (4, 12)), jnp.int32)
    ref_step = jax.jit(_masked_step, static_argnames=("cfg",))

    def admit(arena, tok, rows, lens):
        logits, fresh = tm.prefill(params, prompts, jnp.asarray(lens), cfg, 16)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        return _merge_admitted(arena, fresh, tok, first, jnp.arange(4),
                               jnp.asarray(rows))

    def run(step):
        arena, tok = admit(tm.init_cache(cfg, 4, 16), jnp.zeros(4, jnp.int32),
                           [True, True, True, False], [12, 5, 9, 1])
        trail = []
        for t in range(12):
            if t == 3:
                arena, tok = admit(arena, tok, [False, False, False, True],
                                   [1, 1, 1, 7])
            before = arena
            tok, arena = step(params, arena, tok, cfg=cfg)
            # copies: a host view of a CPU buffer would keep it from donation
            trail.append([np.array(a) for a in jax.tree.leaves((tok, arena))])
            if step is tm.serve_step:  # the arena went to the step
                assert before.k.is_deleted() and before.v.is_deleted()
        return trail

    got, ref = run(tm.serve_step), run(ref_step)
    for t, (g, r) in enumerate(zip(got, ref)):
        assert g[0].tolist() == r[0].tolist(), t  # the tokens
        for a, b in zip(g, r, strict=True):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), t
    assert got[-1][4].tolist() == [24, 17, 21, 16]  # the cursors
