"""The benchmark's operation and byte counts against hand counts."""
import pytest

from bench import flops, spec

QWEN = spec.load_json(spec.ROOT + "/bench/configs/qwen3-4b.json")["config"]
# the paper's evaluation transformer (arXiv 2105.14450, Tables 1-2)
PAPER = dict(num_hidden_layers=4, hidden_size=3072, num_attention_heads=64,
             num_key_value_heads=64, head_dim=48, intermediate_size=12288,
             vocab_size=32000, mlp="plain")


def test_qwen3_4b_matmul_params():
    d, L = 2560, 36
    attn = d * 4096 + 2 * d * 1024 + 4096 * d      # q, k, v (8 x 128), o
    mlp = 3 * d * 9728                             # gate, up, down
    head = d * 151936
    assert flops.matmul_params(QWEN) == L * (attn + mlp) + head
    # 3.63e9 in the blocks plus the 389M head (the embedding is a lookup)
    assert flops.matmul_params(QWEN) == pytest.approx(4.0223e9, rel=1e-4)


def test_paper_transformer_matmul_params():
    d, L = 3072, 4
    per_layer = 4 * d * d + 2 * d * 12288          # q, k, v, o; up, down
    assert flops.matmul_params(PAPER) == L * per_layer + d * 32000
    assert flops.matmul_params(PAPER) == pytest.approx(5.5129e8, rel=1e-4)


def test_decode_flops_count_live_lengths():
    live = [10, 300, 1000]
    attn = 4 * 36 * 32 * 128 * sum(live)
    assert flops.decode_flops(QWEN, live) == \
        2 * flops.matmul_params(QWEN) * 3 + attn


def test_paged_attention_bytes_are_live_kv_and_q_out():
    ops, nbytes = flops.paged_attention(QWEN, [100, 28])
    assert ops == 4 * 36 * 32 * 128 * 128
    kv = 128 * 8 * 128 * 2 * 2                     # tokens x kv x d x K,V x bf16
    qo = 2 * 32 * 128 * (2 + 4)                    # 2 slots, bf16 q, f32 out
    assert nbytes == 36 * (kv + qo)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_time(ops, nbytes, peak)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)
