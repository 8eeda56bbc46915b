"""chip_smoke.py on the CPU at tiny size: every phase function runs and its
checks pass; the driver refuses to run without a TPU or without the repo;
the four-chip phase runs on a forced 4-device CPU mesh; the launcher's
shared builders and the compile-cache helper behave as documented."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR  # noqa: E402


@pytest.fixture(scope="module")
def stack():
    g, emb, pipe = chip_smoke.build_stack(2048, max_len=96, seed=0)
    cfg, params = serve.build_lm(chip_smoke.ARCH, published=False,
                                 vocab_size=pipe.tokenizer.vocab.size)
    return g, emb, pipe, cfg, params


def test_retrieval_phase_tiny(stack):
    _, emb, pipe, _, _ = stack
    out = chip_smoke.phase_retrieval(pipe, emb, n_queries=8, seed=0)
    assert out["exact_id_share"] == 1.0
    assert 0.0 <= out["overflow_share"] <= 1.0


@pytest.mark.parametrize("paged", [False, True])
def test_serving_phase_tiny(stack, paged):
    g, emb, pipe, cfg, params = stack
    done = chip_smoke.phase_serving(pipe, g, emb, params, cfg, paged=paged,
                                    n_requests=4, max_new=6, slots=2, seed=0)
    assert [r.uid for r in done] == [0, 1, 2, 3]
    assert all(len(r.out_tokens) == 6 for r in done)


def test_numerics_phase_tiny(stack):
    g, emb, pipe, cfg, params = stack
    done = chip_smoke.serve_pass(pipe, g, emb, params, cfg, paged=False,
                                 n_requests=2, max_new=6, slots=2, seed=3)
    out = chip_smoke.phase_numerics(params, cfg, done, n_check=2)
    assert out["agreement"] == 1.0  # float32 smoke config: exact agreement


def test_topk_agreement_flags_a_real_swap():
    import numpy as np

    table = jax.numpy.eye(4, dtype=jax.numpy.float32)
    q = np.array([[1.0, 0.5, 0.0, 0.0]], np.float32)
    near = chip_smoke.topk_agreement(q, table, [[0, 1]], [[0, 1]], 1e-2)
    assert near["ok"] and near["exact_share"] == 1.0
    swap = chip_smoke.topk_agreement(q, table, [[0, 1]], [[1, 0]], 1e-2)
    assert not swap["ok"] and swap["max_swap_gap"] == pytest.approx(0.5)


def test_main_refuses_without_tpu(monkeypatch):
    # an env cache dir makes the helper set nothing in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "no TPU" in str(exc.value.code)


def test_smoke_alone_fails_and_prints_no_verdict(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_FOUR_CHIP_SCRIPT = """
import sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke
assert jax.device_count() == 4, jax.device_count()
chip_smoke.phase_four_chips(jax.devices(), rows=3001, dim=100, n_queries=8,
                            k=10, n_clusters=8, seed=0)
print("FOUR_CHIP_OK")
"""


def test_four_chip_phase_on_forced_cpu_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=4", "")
        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIP_SCRIPT.format(root=str(ROOT))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_CHIP_OK" in out.stdout
    # one shard per device, on each of the 4 devices
    assert out.stdout.count("TFRT_CPU_") >= 8, out.stdout


def test_lm_config_keeps_published_vocab():
    pub = serve.lm_config("starcoder2-3b", published=True, vocab_size=1066)
    assert (pub.d_model, pub.n_layers, pub.vocab) == (3072, 30, 49152)
    with pytest.raises(ValueError, match="published vocabulary"):
        serve.lm_config("starcoder2-3b", published=True, vocab_size=49153)
    smoke = serve.lm_config("starcoder2-3b", published=False, vocab_size=1066)
    assert smoke.vocab == 1066 and smoke.n_layers == 2


def test_unserved_names_failed_degraded_and_stale():
    from repro.serving import RAGRequest

    reqs = [RAGRequest(uid=i, query_emb=None, query_text="q")
            for i in range(4)]
    reqs[1].failed, reqs[2].degraded, reqs[3].stale = True, True, True
    assert [r.uid for r in serve.unserved(reqs)] == [1, 2, 3]


_CACHE_SCRIPT = """
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.launch.compile_cache import setup_compile_cache
print("CACHE_DIR=" + setup_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * {salt} + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_fixed_dir(tmp_path, from_env):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    else:
        want = CHECKOUT_CACHE_DIR
    before = set(os.listdir(want)) if want.is_dir() else set()
    salt = 7 + int.from_bytes(os.urandom(3), "little")  # a fresh entry
    out = subprocess.run(
        [sys.executable, "-c",
         _CACHE_SCRIPT.format(src=str(ROOT / "src"), salt=salt)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"CACHE_DIR={want}" in out.stdout
    assert set(os.listdir(want)) - before  # new entries landed there
