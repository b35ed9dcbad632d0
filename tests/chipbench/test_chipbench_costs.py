"""The table of peaks and the operation / byte functions, each against a
case worked by hand."""

import pytest

from chipbench import costs, manifest as mf

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 10}
M7B = mf.read_json(mf.ROOT, "chipbench/configs/mistral-7b-train.json")


def test_peaks_of_the_v5e_and_no_default():
    p = costs.load_peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        costs.load_peaks("TPU v9 imaginary")


def test_matmul_params_by_hand():
    # head_dim 4; per layer q 8*8 + k 8*4 + v 8*4 + o 8*8 = 192, mlp 3*8*16 = 384
    got = costs.matmul_params(TINY)
    assert got == {"layer": 576, "head": 80, "total": 3 * 576 + 80}


def test_mistral_7b_matmul_params():
    full = {**M7B, "num_hidden_layers": 32}
    got = costs.matmul_params(full)
    assert got["layer"] == 218_103_808 and got["head"] == 131_072_000
    # 7.24e9 published parameters less the embedding (131M) and the norms
    assert got["total"] == pytest.approx(7.11e9, rel=0.002)


def test_train_flops_per_token_by_hand():
    # S=4: 10 causal pairs, 3 layers x 2 heads x 4*4 FLOPs a pair = 960 forward
    assert costs.attn_flops_causal(TINY, 4) == 960
    assert costs.train_flops_per_token(TINY, 4) == 6 * 1808 + 3 * 960 / 4


def test_attention_share_of_the_train_cell_at_4096():
    # 2 layers: matmul parameters 2 x 218.1M + 131.1M = 567.3M -> 3.404 GFLOP a token;
    # attention 3 x (2 layers x 32 heads x 4 x 128 x 4097 / 2) = 201.4 MFLOP a token: 5.9%
    matmul = 6 * costs.matmul_params(M7B)["total"]
    total = costs.train_flops_per_token(M7B, 4096)
    assert matmul == 6 * 567_279_616
    assert total - matmul == pytest.approx(3 * 2 * 32 * 512 * 4097 / 2)
    assert (total - matmul) / matmul == pytest.approx(0.0592, abs=0.0005)


def test_flash_cost_by_hand():
    c = costs.flash_cost(TINY, batch=2, seq_len=4)
    assert c["fwd_flops"] == 2 * 2 * 4 * 4 * 10 and c["bwd_flops"] == 2.5 * c["fwd_flops"]
    q, kv = 2 * 4 * 2 * 4 * 2, 2 * 4 * 1 * 4 * 2
    assert c["fwd_bytes"] == 2 * q + 2 * kv and c["bwd_bytes"] == 4 * q + 4 * kv


def test_flash_is_compute_bound_at_the_cells_shape():
    c = costs.flash_cost(M7B, batch=4, seq_len=4096)
    t, bound = costs.roofline_seconds(c["fwd_flops"], c["fwd_bytes"], costs.load_peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(c["fwd_flops"] / 197e12)
    assert costs.roofline_seconds(1.0, 1e6, costs.load_peaks("TPU v5 lite"))[1] == "memory"
