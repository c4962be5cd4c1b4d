"""The ``dsa_moe_lm`` family's arithmetic against hand arithmetic, its
configuration against the catalog's published numbers, and the five
readers of the sparse attention on hand-made inputs."""

import json
import os

import pytest

from benchmark.families import dsa_moe_lm
from benchmark.layer_metrics import (attn_pct, dsa_index_pct,
                                     dsa_index_roofline_pct, dsa_topk_pct,
                                     sparse_flash_pct,
                                     sparse_flash_roofline_pct)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = load("configs", "keye-vl-2.0-30b-a3b")
TRAFFIC = load("traffic", "b1-s32768")
# the catalog's ``config`` of Keye-VL-2.0-30B-A3B, every key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"]


def test_published_widths_are_whole_and_the_cut_is_written_down():
    assert CONFIG["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert CONFIG[key] == value, key
    # the cut: five layers (six did not fit, reduced_how.fit), 16 of 128
    # experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] == 5
    assert CONFIG["num_experts"] == CONFIG["num_local_experts"] == 16
    assert CONFIG["vocab_size"] * 8 == 151936
    assert CONFIG["published"] == {key: PUBLISHED[key] for key in REDUCED}
    deployment = CONFIG["deployment"]
    assert deployment["chips_that_share_each_layer"] == 8
    assert deployment["router_width"] == 128
    assert deployment["expert_offset"] == 0
    assert deployment["recompute_attention"] is True
    assert CONFIG["num_experts"] * 8 == 128
    assert TRAFFIC["per_chip_batch"] == 1 and TRAFFIC["seq_len"] == 32768
    assert TRAFFIC["mesh"] == {"data": 1}
    assumed = CONFIG["assumed"]
    for key in ("qk_norm", "router", "rotary", "indexer_input", "indexer",
                "indexer_loss", "chunk_sizes", "every_layer_sparse",
                "tie_rule"):
        assert assumed[key]
    assert set(CONFIG["reduced_how"]) == set(REDUCED) | {"fit"}
    departures = " ".join(CONFIG["departures"])
    for part in ("vision tower", "M-RoPE", "FP8", "warm-up"):
        assert part in departures, part


def test_parameters_held():
    attention = 2048 * 128 * (32 + 4 + 4) + 32 * 128 * 2048
    assert attention == 18_874_368
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2 * 64 + 2048 * 16
    assert indexer == 2_261_120
    layer = attention + 256 + indexer + 2048 * 128 + 2 * 2048 + (
        16 * 3 * 2048 * 768)
    assert layer == 96_899_456
    total = 5 * layer + 2 * 2048 * 18992 + 2048
    assert total == CONFIG["parameters"] == dsa_moe_lm.parameters(CONFIG)
    assert total == 562_290_560
    # 16 bytes a parameter: 8.38 GiB of state
    assert total * 16 / 2 ** 30 == pytest.approx(8.38, abs=0.005)


def test_token_flops():
    s, k = 32768, 2048
    selected = k * (k + 1) // 2 + (s - k) * k
    assert selected == 65_012_736  # 12.1% of the causal pairs
    assert selected / (s * (s + 1) // 2) == pytest.approx(0.121, abs=1e-3)
    matmul = 6 * (5 * (18_874_368 + 2048 * 128 + 1.0 * 3 * 2048 * 768)
                  + 2048 * 18992)
    sparse = 3 * selected * 4 * 128 * 32
    index = (s * (s + 1) // 2 * 2048 + selected * 4096
             + 4 * (2048 * 16 * 64 + 2048 * 64 + 2048 * 16) * s)
    want = matmul + 5 * (sparse + index) / s
    assert dsa_moe_lm.required_flops_per_item(CONFIG, TRAFFIC) == (
        pytest.approx(want))
    # the attention and the indexer are 44% of the step's required FLOPs
    share = 5 * (sparse + index) / s / want
    assert share == pytest.approx(0.439, abs=1e-3)


def test_kernel_work():
    work = dsa_moe_lm.kernel_work(CONFIG, TRAFFIC)
    s, selected = 32768, 65_012_736
    assert work["sparse_flops"] == 5 * 3 * selected * 4 * 128 * 32
    q, kv, chosen = s * 32 * 128 * 2, s * 4 * 128 * 2, selected * 8
    assert work["sparse_bytes"] == 5 * ((2 * q + 2 * kv + chosen)
                                        + (3 * q + 4 * kv + chosen))
    # the FLOPs bind: 81 ms a step of them against 16 of bytes
    assert work["sparse_flops"] / 197e12 == pytest.approx(0.0811, abs=1e-3)
    assert work["sparse_bytes"] / 819e9 == pytest.approx(0.0158, abs=1e-3)
    assert work["index_flops"] / 197e12 > work["index_bytes"] / 819e9
    # 4,096 expected held token-slots a layer, three products an expert
    slots = s * 8 * 16 / 128
    assert work["grouped_flops"] == 5 * slots * 6 * 3 * 2048 * 768
    product = slots * (2048 + 768) + 16 * 2048 * 768
    assert work["grouped_bytes"] == 5 * 3 * 3 * 2 * product


def _run(table, by_name, window_s=1.0, work=None):
    return {"instruction_table": table, "traced_steps": 2,
            "summary": {"chips": {0: {"by_name": by_name,
                                      "window_s": window_s}}},
            "kernel_work": work if work is not None else {
                "sparse_flops": 197e12 * 0.05, "sparse_bytes": 819e9 * 0.01,
                "index_flops": 197e12 * 0.01, "index_bytes": 819e9 * 0.02},
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}}


def _table(**op_names):
    return {name: {"category": "fusion", "opcode": "fusion",
                   "op_name": op_name}
            for name, op_name in op_names.items()}


def test_the_readers_tell_the_scopes_apart():
    table = _table(
        a="jit(step)/jvp(T)/block_0/attn/hvd_attn_sparse/attn/pallas_call",
        b="jit(step)/transpose(jvp(T))/block_0/jvp(T)/block_0/checkpoint/"
          "attn/hvd_attn_sparse/attn/pallas_call",
        c="jit(step)/jvp(T)/block_0/attn/hvd_dsa_topk/pallas_call",
        d="jit(step)/jvp(T)/block_0/attn/hvd_dsa_index/pallas_call",
        e="jit(step)/jvp(T)/block_1/attn/hvd_dsa_index/wq/dot_general",
        f="jit(step)/jvp(T)/block_1/attn/hvd_attn/query/dot_general",
        g="jit(step)/jvp(T)/block_1/experts/hvd_moe_experts/pallas_call",
        h="jit(step)/jvp(T)/block_1/attn/hvd_dsa_topkx/x")
    run = _run(table, {"a": 0.1, "b": 0.15, "c": 0.03, "d": 0.02,
                       "e": 0.03, "f": 0.2, "g": 0.3, "h": 0.05})
    assert sparse_flash_pct.read(run) == pytest.approx(25.0)
    assert dsa_topk_pct.read(run) == pytest.approx(3.0)
    assert dsa_index_pct.read(run) == pytest.approx(5.0)
    assert attn_pct.read(run) == pytest.approx(20.0)  # no kernel, no index
    # the FLOPs bind: 0.05 s a step, two steps, 0.25 s under the scope
    assert sparse_flash_roofline_pct.read(run) == pytest.approx(40.0)
    # the bytes bind: 0.02 s a step, two steps, 0.08 s under both scopes
    assert dsa_index_roofline_pct.read(run) == pytest.approx(50.0)


def test_what_the_parent_lacks_is_left_out_not_zero(capsys):
    """What the parent commit's program gives these readers: no
    instruction under any of the three scopes, so the line leaves the
    metrics out; a family that states no such work has nothing to divide
    by."""
    run = _run(_table(a="jit(step)/jvp(T)/block_0/attn/pallas_call"),
               {"a": 1.0})
    for reader in (sparse_flash_pct, dsa_topk_pct, dsa_index_pct,
                   sparse_flash_roofline_pct, dsa_index_roofline_pct):
        assert reader.read(run) is None
    assert "hvd_dsa_topk" in capsys.readouterr().err
    table = _table(
        a="jit(step)/jvp(T)/attn/hvd_attn_sparse/attn/pallas_call",
        b="jit(step)/jvp(T)/attn/hvd_dsa_topk/pallas_call",
        c="jit(step)/jvp(T)/attn/hvd_dsa_index/pallas_call")
    for work in ({"full_flops": 1.0, "full_bytes": 1.0}, None):
        run = _run(table, {"a": 1.0, "b": 1.0, "c": 1.0})
        run["kernel_work"] = work
        assert sparse_flash_roofline_pct.read(run) is None
        assert dsa_index_roofline_pct.read(run) is None
        assert sparse_flash_pct.read(run) == pytest.approx(100.0)
