"""The benchmark's operation and byte counts against hand counts, and its
table of peaks."""
import pytest

from bench import flops, models

llama = models.family({"architectures": ["LlamaForCausalLM"]})
TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 10, "torch_dtype": "bfloat16"}


def test_topk_sim_hand_count():
    # 3 queries x 5 rows x 2 dims: 30 multiply-adds; the table (40 B) and
    # queries (24 B) read once, 3 x 2 scores and ids written (48 B)
    f, b = flops.topk_sim(q=3, n=5, d=2, k=2)
    assert f == 60
    assert b == 40 + 24 + 48


def test_decoder_token_flops_hand_count():
    # per layer: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + 3 x 8x16 = 576;
    # 2 layers + head 8x10 = 1232 weights, 2 flops each
    assert llama.matmul_params(TINY) == 1232
    assert llama.token_flops(TINY) == 2464


def test_attention_and_prefill_hand_count():
    # one query over 3 keys: 2 layers x 2 heads x (2 x 3 x 4) x 2
    assert llama.attn_flops(TINY, 3) == 192
    # prefill of 3 tokens sees 1 + 2 + 3 keys
    assert llama.prefill_flops(TINY, 3) == 3 * 2464 + 64 * 6
    assert llama.decode_flops(TINY, 2) == 2464 + 192


def test_sliding_window_caps_keys():
    m = dict(TINY, sliding_window=2)
    assert llama.attn_flops(m, 5) == llama.attn_flops(TINY, 2)
    # 4 tokens under a window of 2: keys 1 + 2 + 2 + 2
    assert llama.prefill_flops(m, 4) == 4 * 2464 + 64 * 7


def test_roofline_names_its_bound():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000, 10, pk) == (10.0, "compute")
    assert flops.roofline_s(10, 1000, pk) == (100.0, "memory")


def test_peaks_are_keyed_by_device_kind():
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
