"""Every model family file in ``bench/models/`` against the program at a
tiny size on the CPU, and the Llama family's readings pinned from before
its code moved there.

A family is tested at the fixture configuration under
``tests/bench/fixtures/`` whose ``model["architectures"]`` names it; a
family file added without one fails here.
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import models
from benchutil import FIXTURES, ROOT

FAMILIES = models.known()
TOKENS = np.random.default_rng(0).integers(6, 1000, 64).astype(np.int32)


def _tiny_model(arch: str, **kw) -> dict:
    for path in sorted(FIXTURES.glob("*.json")):
        model = json.loads(path.read_text()).get("model")
        if isinstance(model, dict) and model.get("architectures") == [arch]:
            return dict(model, **kw)
    raise LookupError(f"no fixture configuration names {arch!r}")


def test_every_family_is_listed():
    assert "LlamaForCausalLM" in FAMILIES
    for arch in FAMILIES:
        fam = models.family({"architectures": [arch]})
        for name in ("program_config", "make_params", "hparams", "lm_logits",
                     "prefill_flops", "decode_flops"):
            assert callable(getattr(fam, name)), (arch, name)


@pytest.mark.parametrize("window", [4096, 8], ids=["full", "window8"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_lm_reference_matches_the_program_forward(arch, window):
    """The program's forward through ``program_config`` against the
    family's float32 reference; the float8 control departs from it."""
    from repro.models.transformer import model as tm

    model = _tiny_model(arch, sliding_window=window)
    fam = models.family(model)
    cfg = fam.program_config(model, "tiny")
    params = fam.make_params(model, 3)
    toks = jnp.asarray(TOKENS)
    prog = np.asarray(tm.lm_logits(params, toks[None], cfg))[0]
    ref = np.asarray(fam.lm_logits(params, toks, fam.hparams(model)))
    assert np.abs(prog - ref).max() < 1e-3
    ctl = np.asarray(fam.lm_logits(params, toks, fam.hparams(model),
                                   quant=True))
    assert np.abs(ctl - ref).max() > 1e-2


@pytest.mark.parametrize("window", [4096, 8], ids=["full", "window8"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        arch, window):
    """A padded prefill of 40 tokens, then 24 decode steps through the
    program's cache, against the reference's one full forward pass."""
    from repro.models.transformer import model as tm

    model = _tiny_model(arch, sliding_window=window)
    fam = models.family(model)
    cfg = fam.program_config(model, "tiny")
    params = fam.make_params(model, 3)
    n_prompt, bucket, cache_len = 40, 48, 128
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = TOKENS[:n_prompt]
    logits, cache = tm.prefill(params, jnp.asarray(padded),
                               jnp.asarray([n_prompt], jnp.int32), cfg,
                               cache_len)
    got = [np.asarray(logits)[0]]
    step = jax.jit(tm.decode_step, static_argnames=("cfg",))
    for t in TOKENS[n_prompt:]:
        logits, cache = step(params, cache, jnp.asarray([t], jnp.int32), cfg)
        got.append(np.asarray(logits)[0])
    ref = np.asarray(fam.lm_logits(params, jnp.asarray(TOKENS),
                                   fam.hparams(model)))
    assert np.abs(np.stack(got) - ref[n_prompt - 1:]).max() < 1e-3


@pytest.mark.parametrize("window", [None, 5], ids=["full", "window5"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_prefill_flops_are_the_sum_of_decode_flops(arch, window):
    """A causal prefill of L tokens is the work of decoding positions
    0 .. L-1 one at a time."""
    model = _tiny_model(arch, sliding_window=window)
    fam = models.family(model)
    for length in (1, 4, 5, 6, 33):
        assert fam.prefill_flops(model, length) == sum(
            fam.decode_flops(model, p) for p in range(length))


def test_llama_family_keeps_the_pinned_readings():
    """Weights, reference logits and flop counts of the Llama family, as
    read before its code moved into ``bench/models/``."""
    pin = json.loads((FIXTURES / "pinned-LlamaForCausalLM.json").read_text())
    tiny = json.loads((FIXTURES / "tiny.json").read_text())["model"]
    fam = models.family(tiny)
    params = fam.make_params(tiny, pin["seed"])
    hashes = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(x).tobytes()).hexdigest()
        for k, x in jax.tree_util.tree_leaves_with_path(params)}
    assert hashes == pin["weights_sha256"]
    toks = jnp.asarray(TOKENS[:32])
    lg = np.asarray(fam.lm_logits(params, toks, fam.hparams(tiny)))
    np.testing.assert_allclose(lg[pin["logit_rows"], :12], pin["logits"],
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(lg.max(-1), pin["rowmax"], rtol=0, atol=1e-5)
    assert lg.argmax(-1).tolist() == pin["argmax"]
    dsk = json.loads((ROOT / "bench" / "configs" /
                      "dsk-7b-l15-arxiv.json").read_text())["model"]
    want = pin["dsk-7b-l15-arxiv"]
    assert fam.token_flops(dsk) == want["token_flops"]
    assert fam.prefill_flops(dsk, 512) == want["prefill_flops_512"]
    assert fam.decode_flops(dsk, 700) == want["decode_flops_700"]
