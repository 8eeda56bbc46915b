"""The harness's inner loop end to end at a tiny size on the CPU, the
faults its comparison must catch, finding a cell and a metric by name,
and the CLI's refusal of a CPU."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import models
from benchutil import ROOT, tiny_cell

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 33 + 5


def _run(mix_name, tmp_path, seconds=1.5, **kw):
    import bench.run as R

    return R.run(tiny_cell(mix_name, **kw), SEED, seconds, False,
                 jax.devices(), PEAKS, corpus_dir=tmp_path)


@pytest.mark.parametrize("mix_name", ["tiny-closed", "tiny-open"])
def test_inner_loop_end_to_end(mix_name, tmp_path):
    out = _run(mix_name, tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert m["setup_s"]["value"] > 0
    if mix_name == "tiny-closed":
        assert m["tokens_per_s"]["value"] > 0 and "ttft_p95_ms" not in m
    else:
        assert 0 < m["ttft_p50_ms"]["value"] <= m["ttft_p95_ms"]["value"]
        assert m["tpot_p95_ms"]["value"] > 0 and "tokens_per_s" not in m
    assert out["checks"]["logit_gap_max"]["value"] < 1e-3


def _state_unchanged(orig):
    def step(params, cache, token, cfg):
        nxt, _ = orig(params, cache, token, cfg)
        return nxt, cache
    return step


def _token_altered(orig):
    def step(params, cache, token, cfg):
        nxt, cache = orig(params, cache, token, cfg)
        return nxt.at[0].set((nxt[0] + 7) % cfg.vocab), cache
    return step


def _half_batch_left_out(orig):
    def step(params, cache, token, cfg):
        nxt, cache = orig(params, cache, token, cfg)
        rows = jnp.arange(nxt.shape[0]) >= nxt.shape[0] // 2
        return jnp.where(rows, token.reshape(nxt.shape), nxt), cache
    return step


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _token_altered, _half_batch_left_out],
    ids=["state_unchanged", "token_altered", "half_batch_left_out"])
def test_decode_faults_come_out_incorrect(fault, tmp_path, monkeypatch):
    from repro.models.transformer import model as tm

    monkeypatch.setattr(tm, "serve_step", jax.jit(
        fault(tm.serve_step), static_argnames=("cfg",)))
    out = _run("tiny-closed", tmp_path)
    assert out["correct"] is False
    assert out["checks"]["logit_gap_max"]["value"] > \
        out["checks"]["logit_gap_max"]["limit"]


@pytest.mark.parametrize("seed", [SEED, 7, 11])
def test_control_comes_out_incorrect(seed, tmp_path):
    """The reference in float8 put in the program's place reads a gap past
    the limit, while the program itself stays under it."""
    from bench import calibrate

    cell = tiny_cell("tiny-closed")
    b = H.build(cell, seed, tmp_path)
    row = calibrate.reading(cell, b, seed, 2.0)
    limit = cell.config["correct"]["gap_limit"]
    assert row["unserved"] == 0 and row["retrieval_faults"] == 0
    assert row["gap"] <= limit < row["control_gap"]


def _control_tokens(params, cell, rec, max_new):
    """Greedy tokens of the float8 control for one served request's prompt,
    as many as it was served."""
    hp = cell.family.hparams(cell.config["model"])
    plen, n = len(rec.req.prompt_ids), len(rec.req.out_tokens)
    seq = np.zeros(H.pad_len(cell.config, max_new), np.int32)
    seq[:plen] = rec.req.prompt_ids
    out = []
    for i in range(n):
        logits = cell.family.lm_logits(params, jnp.asarray(seq), hp,
                                       quant=True)
        out.append(int(jnp.argmax(logits[plen - 1 + i])))
        seq[plen + i] = out[-1]
    return out


@pytest.mark.parametrize("seed", [SEED, 7, 11])
def test_control_in_the_programs_place_comes_out_incorrect(seed, tmp_path,
                                                           monkeypatch):
    """The float8 control decodes the sampled requests in the program's
    place, and run.py's own checks come out not correct."""
    import bench.run as R

    cell = tiny_cell("tiny-closed")
    built = {}
    build, sample = H.build, H.sample_checks

    def keep_build(*a, **k):
        built["b"] = build(*a, **k)
        return built["b"]

    def control_sample(done, seed_, check_tokens):
        lm, ret = sample(done, seed_, check_tokens)
        max_new = max(r.spec.max_new for r in done)
        for r in lm:
            r.req.out_tokens = _control_tokens(built["b"].params, cell, r,
                                               max_new)
        return lm, ret

    monkeypatch.setattr(H, "build", keep_build)
    monkeypatch.setattr(H, "sample_checks", control_sample)
    out = R.run(cell, seed, 1.5, False, jax.devices(), PEAKS,
                corpus_dir=tmp_path)
    gap = out["checks"]["logit_gap_max"]
    assert out["correct"] is False and gap["value"] > gap["limit"]
    assert out["checks"]["retrieval_faults"]["value"] == 0


def test_altered_retrieval_answer_comes_out_incorrect(tmp_path, monkeypatch):
    from repro.core import filters

    orig = filters.dynamic_filter

    def drop_last(sub, scores, seeds, *, budget):
        out = orig(sub, scores, seeds, budget=budget)
        return out.__class__(nodes=out.nodes, mask=out.mask.at[:, -1].set(False),
                             dist=out.dist, num_nodes=out.num_nodes,
                             overflow=out.overflow)

    monkeypatch.setattr(filters, "dynamic_filter", drop_last)
    out = _run("tiny-closed", tmp_path)
    assert out["correct"] is False
    assert out["checks"]["retrieval_faults"]["value"] > 0


def test_cli_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "dsk-7b-l15-arxiv.closed-lookup", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# a family file that a later configuration could add: another architecture
# name, served by the program's Llama block
TOY_FAMILY = """
from bench.models import family

_llama = family({"architectures": ["LlamaForCausalLM"]})
program_config = _llama.program_config
make_params = _llama.make_params
hparams = _llama.hparams
lm_logits = _llama.lm_logits
prefill_flops = _llama.prefill_flops


def decode_flops(model, position):
    return 7 + _llama.decode_flops(model, position)
"""


def _bench_tree(root, config: dict, mix: dict):
    """A BENCHMARK.json with one cell, ``new-model.new-mix``, under
    ``root``, and the configuration, mix and metric files it names."""
    bench = root / "bench"
    for d in ("configs", "traffic", "metrics", "models"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    (bench / "configs" / "new-model.json").write_text(json.dumps(
        dict(config, name="new-model")))
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new_metric.cell.py").write_text(
        "def read(run):\n    return 42.0\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "new-model",
                     "file": "bench/configs/new-model.json"}],
        "workloads": [{"name": "new-model.new-mix", "config": "new-model",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "tokens_per_s", "unit": "tokens/s",
                        "workloads": ["new-model.new-mix"]},
                       {"name": "other", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "new_metric.cell", "unit": "%",
                       "moves": "tokens_per_s"},
                      {"name": "ttft_only", "unit": "%",
                       "moves": "ttft_p95_ms"}]}))
    return bench


def test_cells_configs_mixes_and_metrics_are_found_by_name(tmp_path):
    bench = _bench_tree(tmp_path,
                        {"model": {"architectures": ["NewForCausalLM"]}},
                        {"loop": "closed", "clients": 2})
    (bench / "models" / "NewForCausalLM.py").write_text(
        "def program_config(model, name):\n    return name\n")
    cell = H.load_cell("new-model.new-mix", root=tmp_path)
    assert cell.config["name"] == "new-model"
    assert cell.mix["clients"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "tokens_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric.cell"]
    assert cell.family.program_config({}, "x") == "x"
    read = H.metric_reader("new_metric.cell", bench / "metrics")
    assert read(None) == 42.0
    with pytest.raises(KeyError):
        H.load_cell("missing", root=tmp_path)


def test_model_family_is_found_by_architecture(tiny_config, tmp_path):
    """A family file added under a new architecture name is found, built,
    served, counted and checked with no edit to the harness."""
    import bench.run as R
    from benchutil import FIXTURES

    model = dict(tiny_config["model"], architectures=["ToyForCausalLM"])
    bench = _bench_tree(
        tmp_path, dict(tiny_config, model=model),
        json.loads((FIXTURES / "tiny-closed.json").read_text()))
    (bench / "models" / "ToyForCausalLM.py").write_text(TOY_FAMILY)
    cell = H.load_cell("new-model.new-mix", root=tmp_path)
    assert cell.family.__file__ == str(bench / "models" / "ToyForCausalLM.py")
    out = R.run(cell, SEED, 1.5, False, jax.devices(), PEAKS,
                corpus_dir=tmp_path / "corpus")
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["logit_gap_max"]["value"] < 1e-3
    # served work is counted by the cell's family: a 10-token prompt and
    # two decoded tokens
    rec = H.Recorder(None, cell, [])
    req = type("Req", (), {"prompt_ids": [0] * 10})()
    rec._gain(H.Rec(uid=0, spec=None, req=req, due=0.0, in_window=True),
              3, 0.0)
    llama = models.family(tiny_config["model"])
    assert rec.flops == llama.prefill_flops(model, 10) + sum(
        llama.decode_flops(model, p) + 7 for p in (10, 11))


@pytest.mark.parametrize("model", [{}, {"architectures": ["NoSuchLM"]}],
                         ids=["missing", "unknown"])
def test_unnamed_or_unknown_architecture_is_refused(model, tmp_path):
    bench = _bench_tree(tmp_path, {"model": model},
                        {"loop": "closed", "clients": 2})
    (bench / "models" / "ToyForCausalLM.py").write_text(TOY_FAMILY)
    with pytest.raises(KeyError, match=r"known: \['ToyForCausalLM'\]"):
        H.load_cell("new-model.new-mix", root=tmp_path)
    with pytest.raises(KeyError, match="LlamaForCausalLM"):
        models.family(model)


def test_every_listed_metric_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(H.metric_reader(m["name"])), m["name"]
    for w in spec["workloads"]:
        cell = H.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
