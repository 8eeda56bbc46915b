"""The DeepSeek-V2 family against the program at a tiny size on the CPU:
the held shares of a MoE layer against the uncut reference layer, dropless
routing under skew, the routing counters, and the whole serving path of a
tiny V2 cell.  (The family's contract cases are in test_bench_models.py.)"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import models, traffic
from benchutil import FIXTURES

FIXTURE = json.loads((FIXTURES / "tiny-DeepseekV2ForCausalLM.json")
                     .read_text())
MODEL = FIXTURE["model"]
FAM = models.family(MODEL)
E, K, D, F, FS = 8, 3, 64, 32, 64  # the fixture's routed experts and widths


def _layer(seed=0):
    """One MoE layer with all E experts, in the program's layout."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, s, sc: jax.random.normal(k, s) * sc
    return {"router": n(ks[0], (D, E), D ** -0.5),
            "w1": n(ks[1], (E, D, F), D ** -0.5),
            "w3": n(ks[2], (E, D, F), D ** -0.5),
            "w2": n(ks[3], (E, F, D), F ** -0.5),
            "s1": n(ks[4], (D, FS), D ** -0.5),
            "s3": n(ks[5], (D, FS), D ** -0.5),
            "s2": n(ks[6], (FS, D), FS ** -0.5)}


def _share(layer, first, held, shared=True):
    out = {k: layer[k][first:first + held] for k in ("w1", "w3", "w2")}
    out["router"] = layer["router"]
    if shared:
        out.update({k: layer[k] for k in ("s1", "s3", "s2")})
    return out


def _moe_cfg(first, held, shared=True):
    from repro.models.transformer.config import MoEConfig

    return MoEConfig(n_experts=E, top_k=K, d_ff=F, capacity_factor=None,
                     n_held=held, first_held=first,
                     d_shared=FS if shared else 0, norm_topk=False)


@pytest.mark.parametrize("tokens", [40, 300], ids=["dense", "sorted"])
@pytest.mark.parametrize("held", [2, 4])
def test_held_shares_add_up_to_the_uncut_layer(held, tokens, monkeypatch):
    """E / held chips each compute their experts' part; with the shared
    experts counted once the parts add up to the uncut reference layer."""
    from repro.models.transformer import moe

    monkeypatch.setattr(moe, "TOKEN_CHUNK", 128)  # 300 tokens: 3 chunks
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, D))
    parts = [moe.moe_held(_share(layer, f, held, shared=False), x,
                          _moe_cfg(f, held, shared=False))[0]
             for f in range(0, E, held)]
    shared = moe.swiglu(x, layer["s1"], layer["s3"], layer["s2"])
    uncut = FAM.moe_layer(layer, x, (E, K, 0, E, False))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(uncut), atol=2e-5)
    # and one chip's share, shared experts included, is the reference's
    one = moe.moe_held(_share(layer, held, held), x, _moe_cfg(held, held))[0]
    np.testing.assert_allclose(
        np.asarray(one),
        np.asarray(FAM.moe_layer(_share(layer, held, held), x,
                                 (E, K, held, held, False))), atol=2e-5)


@pytest.mark.parametrize("tokens", [64, 300], ids=["dense", "sorted"])
def test_dropless_routing_under_skew_matches_the_reference(tokens):
    """Every token picks the same top-k experts, all held here: each of them
    gets every token.  The dropless layer matches the reference; the
    capacity-capped layer drops what is past its capacity."""
    from repro.models.transformer import moe

    layer = _layer(1)
    bias = jnp.where(jnp.arange(E) < K, 4.0, -4.0)
    layer["router"] = jnp.broadcast_to(bias, (D, E)) / D
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (tokens, D))) + 0.5
    share = _share(layer, 0, 4)
    got, _, load = moe.moe_held(share, x, _moe_cfg(0, 4))
    want = FAM.moe_layer(share, x, (E, K, 0, 4, False))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.asarray(load).tolist() == [tokens] * K + [0] * (4 - K)
    from repro.models.transformer.config import MoEConfig

    capped, _ = moe.moe_ffn(
        {k: layer[k] for k in ("router", "w1", "w3", "w2")}, x,
        MoEConfig(n_experts=E, top_k=K, d_ff=F))
    full = FAM.moe_layer(_share(layer, 0, E, shared=False), x,
                         (E, K, 0, E, True))
    assert np.abs(np.asarray(capped) - np.asarray(full)).max() > 1e-2


def test_reference_yarn_is_the_programs():
    from repro.models.transformer.attention import yarn_inv_freq

    yarn = FAM.hparams(MODEL)[8]
    for dim in (8, 64):
        np.testing.assert_allclose(
            FAM.yarn_inv_freq(dim, 10000.0, yarn),
            yarn_inv_freq(dim, 10000.0, *yarn[:4]), rtol=1e-12)
    cfg = FAM.program_config(MODEL, "t")
    assert FAM.softmax_scale(24, yarn) == pytest.approx(cfg.mla.softmax_scale)


def test_decode_bytes_of_the_benchmark_configuration():
    """Every matrix a decode step multiplies, once, and 31,104 B of latent
    cache a position (27 layers x (512 + 64) x 2 B)."""
    from benchutil import ROOT

    model = json.loads((ROOT / "bench" / "configs" /
                        "dsv2-lite-e8-arxiv.json").read_text())["model"]
    assert FAM.cache_row_bytes(model) == 31_104
    # 27 x 13,762,560 (MLA) + 67,239,936 (dense layer 0) + 26 x (131,072
    # router + 17,301,504 shared + 8 x 8,650,752 held) + 209,715,200 head
    assert FAM.matrix_params(model) == 2_901_147_648
    assert FAM.decode_bytes(model, 64, 800) == \
        2 * 2_901_147_648 + 64 * 800 * 31_104
    # held experts count at their expectation, 6 x 8 / 64 = 0.75 a token
    assert FAM.token_flops(model) - FAM.token_flops(
        dict(model, n_routed_experts=0)) == 2 * 26 * 3 * 2048 * 1408 * 3 // 4


def test_benchmark_configuration_states_its_source_keys_at_the_top():
    """The source's config keys stand at the top level of the file, as the
    source gives them, and agree with the ``model`` block that is run."""
    from benchutil import ROOT

    conf = json.loads((ROOT / "bench" / "configs" /
                       "dsv2-lite-e8-arxiv.json").read_text())
    model = conf["model"]
    top = {k: v for k, v in conf.items() if k in model}
    assert {"first_k_dense_replace", "n_routed_experts", "rope_scaling",
            "kv_lora_rank", "num_hidden_layers"} <= set(top)
    assert all(model[k] == v for k, v in top.items())
    assert set(conf["reduced"]) >= {"n_routed_experts"}
    assert conf["n_routed_experts"] == 8
    assert model["n_routed_experts_published"] == 64


def _cell(mix_name="tiny-closed"):
    return H.Cell(name=f"tiny-dsv2.{mix_name}", config=FIXTURE,
                  mix=traffic.load_mix(FIXTURES / f"{mix_name}.json"),
                  chips=1,
                  end_to_end=[{"name": "setup_s", "unit": "s"},
                              {"name": "tokens_per_s", "unit": "tokens/s"}],
                  per_layer=[], family=FAM)


def test_serving_a_tiny_v2_cell_end_to_end(tmp_path):
    """RAGServeEngine on the contiguous arena serves the tiny V2 cell:
    every sampled served token is the reference's best within 1e-3."""
    import bench.run as R

    out = R.run(_cell(), 2 ** 33 + 9, 1.5, False, jax.devices(),
                {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                corpus_dir=tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["logit_gap_max"]["value"] < 1e-3
    assert out["metrics"]["tokens_per_s"]["value"] > 0


class _CountingNumpy:
    """numpy with ``asarray`` recording the shape of every device array
    it pulls to the host."""

    def __init__(self):
        self.pulled = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **k):
        if isinstance(x, jax.Array):
            self.pulled.append(tuple(x.shape))
        return np.asarray(x, *a, **k)


def test_routing_counters_count_a_known_routing_without_a_read(monkeypatch):
    """With a zero router every token's scores tie and greedy top-3 takes
    experts 0, 1, 2 (ties go to the lower index): after n decode steps of
    S slots the counters hold n x S x 2 MoE layers for each of them and 0
    for expert 3, and the largest one-layer load is S.  No decode step
    pulls them to the host; ``decode_stats`` reads them once."""
    from repro.serving import Request, ServeEngine
    from repro.serving import engine as engine_mod

    cfg = FAM.program_config(MODEL, "tiny")
    params = FAM.make_params(MODEL, 4)
    params["layers"]["moe"]["router"] = jnp.zeros_like(
        params["layers"]["moe"]["router"])
    slots = 3
    eng = ServeEngine(params, cfg, slots=slots, cache_len=64)
    for u in range(slots):
        eng.submit(Request(uid=u, prompt_ids=list(range(6, 12 + u)),
                           max_new_tokens=6))
    spy = _CountingNumpy()
    monkeypatch.setattr(engine_mod, "np", spy)
    eng.run_to_completion()
    assert eng.decode_steps == 5
    assert set(spy.pulled) == {(slots,)}  # tokens and first tokens only
    st = eng.decode_stats()
    n = eng.decode_steps * slots * (cfg.n_layers - 1)
    assert st["routed_per_expert"] == [n, n, n, 0]
    assert st["max_expert_load"] == slots
    assert (4,) in spy.pulled


def test_decode_roofline_reader():
    """Least time of a decode step (bytes over bandwidth here) over the
    device time per serve_step call; a family without decode_bytes reads
    nothing."""
    from types import SimpleNamespace

    from bench import trace as tr
    from benchutil import ROOT

    ms = 1_000_000
    trace = tr.Trace(
        modules=[("jit_serve_step", 10 * ms, 10 * ms, "/device:TPU:0"),
                 ("jit_serve_step", 30 * ms, 10 * ms, "/device:TPU:0")],
        ops=[], host=[("bench:window", 0, 100 * ms)], devices=1)
    model = json.loads((ROOT / "bench" / "configs" /
                        "dsv2-lite-e8-arxiv.json").read_text())["model"]
    rec = SimpleNamespace(req=SimpleNamespace(prompt_ids=[0] * 100),
                          n_tokens=3)  # two decoded tokens: 101, 102 keys
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = SimpleNamespace(
        trace=trace, trace_window=(0, 100 * ms), recs=[rec], peaks=pk,
        counters={"decode_steps": 2, "emitted_tokens": 130, "prefills": 2},
        cell=SimpleNamespace(family=FAM, config={"model": model}))
    read = H.metric_reader("decode_roofline.closed")
    want = 100 * FAM.decode_bytes(model, 64, 101.5) / 819e9 / 0.010
    assert read(run) == pytest.approx(want)
    assert 0 < read(run) < 100
    run.cell.family = models.family({"architectures": ["LlamaForCausalLM"]})
    assert read(run) is None
