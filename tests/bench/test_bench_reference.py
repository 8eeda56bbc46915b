"""The plain references against the program at a tiny size (CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import models, reference
from bench import harness as H


def test_served_gaps_score_the_right_rows(tiny_config):
    model = tiny_config["model"]
    fam = models.family(model)
    params = fam.make_params(model, 4)
    hp = fam.hparams(model)
    prompt = np.arange(6, 40, dtype=np.int32)
    # greedy continuation from the reference itself: every gap is 0
    seq = list(prompt)
    for _ in range(5):
        lg = fam.lm_logits(params, jnp.asarray(np.array(seq, np.int32)), hp)
        seq.append(int(np.argmax(np.asarray(lg)[-1])))
    out = seq[len(prompt):]
    gaps, ctl = reference.request_gaps(fam, params, hp, prompt, out,
                                       pad_to=128, quant_control=True)
    assert gaps.shape == (5,) and np.abs(gaps).max() < 1e-4
    assert ctl.shape == (5,) and (ctl >= 0).all()
    bad = list(out)
    bad[2] = (bad[2] + 1) % 2048
    gaps_bad, _ = reference.request_gaps(fam, params, hp, prompt, bad,
                                         pad_to=128)
    assert gaps_bad[2] > 1e-3


@pytest.fixture(scope="module")
def tiny_stack(tmp_path_factory, tiny_config):
    cell = H.Cell(name="tiny", config=tiny_config, mix={}, chips=1,
                  end_to_end=[], per_layer=[],
                  family=models.family(tiny_config["model"]))
    b = H.build(cell, seed=5, corpus_dir=tmp_path_factory.mktemp("corpus"))
    ref = reference.RetrievalReference(
        b.corpus, b.texts, tiny_config["retrieval"], tiny_config["serving"],
        tiny_config["correct"]["tie_tol"])
    rng = np.random.default_rng(0)
    nodes = rng.choice(b.corpus.num_nodes, 8, replace=False)
    q = b.corpus.feat[nodes] + 0.1 * rng.standard_normal(
        (8, b.corpus.feat.shape[1])).astype(np.float32)
    res = b.pipe.retrieve_many(q)
    texts = [" ".join(b.texts[n].split()[:4]) for n in nodes]
    got = []
    for i in range(8):
        m = np.asarray(res.mask[i])
        kept = np.asarray(res.nodes[i])[m]
        ids, mask = b.pipe.tokenizer.linearize(
            texts[i], [b.texts[v] for v in kept])
        got.append((q[i], texts[i], kept, ids[mask]))
    return ref, got


def test_retrieval_reference_matches_the_program(tiny_stack):
    ref, got = tiny_stack
    for q, text, kept, prompt in got:
        assert ref.faults(q, text, kept, prompt) == []


def test_retrieval_reference_flags_altered_answers(tiny_stack):
    ref, got = tiny_stack
    q, text, kept, prompt = got[0]
    swapped = kept.copy()
    swapped[-1] = (swapped[-1] + 1) % ref.n
    assert ref.faults(q, text, swapped, prompt)
    dropped = kept[:-1]
    assert ref.faults(q, text, dropped, prompt)
    reordered = np.concatenate([kept[:4], kept[4:][::-1]])
    assert ref.faults(q, text, reordered, prompt)
    bad_prompt = prompt.copy()
    bad_prompt[5] += 1
    assert ref.faults(q, text, kept, bad_prompt)
    other_seed = kept.copy()
    other_seed[0] = kept[-1]
    assert ref.faults(q, text, other_seed, prompt)
