"""FLOP and byte counts of the kernels and steps, against hand counts."""
import pytest

from bench import costs
from bench.cell import load_config
from bench.families import transformer
from bench.families.transformer import Dims

# a small GQA shape whose counts are easy by hand
D = Dims(n_layers=2, d=8, heads=4, kv_heads=2, d_head=2, d_ff=16, vocab=10,
         eps=1e-6, rope_theta=1e4, qk_norm=False)
MOE = Dims(n_layers=1, d=8, heads=4, kv_heads=2, d_head=2, d_ff=4, vocab=10,
           eps=1e-6, rope_theta=1e4, qk_norm=False, experts=5, top_k=2)


def test_attention_flops_causal():
    # 3 queries at positions 4, 5, 6 see 5 + 6 + 7 = 18 keys; 4 FLOPs per
    # head-dim element per key (QK and PV), 4 heads x 2 dims
    assert costs.attn_flops(D, 4, 3) == 18 * 4 * 4 * 2
    assert costs.attn_flops(D, 0, 1) == 1 * 32


def test_paged_decode_reads_whole_pages():
    flops, nbytes = costs.paged_decode(D, [1, 64, 65])
    assert flops == (1 + 64 + 65) * 32
    page = 64 * 2 * 2 * 2            # tokens x kv heads x d_head x bf16
    assert nbytes == 2 * (1 + 1 + 2) * page + 3 * 2 * 4 * 2 * 2


def test_extend_and_flash_bytes():
    f, b = costs.paged_extend(D, 100, 30)          # context 130: 3 pages
    assert f == costs.attn_flops(D, 100, 30)
    assert b == 2 * 30 * 8 * 2 + 2 * 3 * 64 * 4 * 2
    f, b = costs.flash_prefill(D, 10)
    assert f == 4 * 8 * (10 * 11 // 2)
    assert b == 2 * 10 * 8 * 2 + 2 * 10 * 4 * 2


def test_moe_gmm_counts_routed_rows_and_every_expert():
    f, b = costs.moe_gmm(MOE, tokens=3)             # 6 routed rows
    assert f == 2 * 6 * 8 * 4
    assert b == 5 * 8 * 4 * 2 + 6 * (8 + 4) * 2


def test_layer_params_dense_and_moe():
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8
    assert D.layer_matmul_params() == attn + 3 * 8 * 16
    assert MOE.layer_matmul_params() == attn + 8 * 5 + 2 * 3 * 8 * 4


def test_step_flops():
    per_row = 2 * (2 * D.layer_matmul_params() + 8 * 10)
    assert transformer.decode_step_flops(D, [5, 9]) == \
        2 * per_row + 2 * (5 + 9) * 32
    assert transformer.prefill_chunk_flops(D, 16, 4) == \
        2 * 2 * D.layer_matmul_params() * 4 + 2 * 8 * 10 \
        + 2 * costs.attn_flops(D, 16, 4)


def test_least_time_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(1000, 10, peaks) == (10.0, "compute")
    assert costs.least_time(10, 1000, peaks) == (100.0, "memory")


@pytest.mark.parametrize("name,params", [("qwen3-8b-l18", 4.72e9),
                                         ("granite-moe-3b-a800m", 3.37e9)])
def test_config_sizes(name, params):
    d = transformer.dims(load_config(name))
    V, L = d.padded_vocab, d.n_layers
    norms = (2 * d.d + (2 * d.d_head if d.qk_norm else 0)) * L + d.d
    total = 2 * V * d.d + norms + L * (d.layer_matmul_params()
                                       + (d.experts - d.top_k) * 3 * d.d
                                       * d.d_ff)
    assert total == pytest.approx(params, rel=0.01)
