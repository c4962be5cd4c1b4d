"""The ``kda_moe_lm`` family's arithmetic against hand arithmetic, its
configuration against the catalog's published numbers, and the three
delta-attention readers on hand-made inputs."""

import json
import os

import pytest

from benchmark.families import kda_moe_lm
from benchmark.harness import scope_time
from benchmark.layer_metrics import (kda_pct, kda_scan_pct,
                                     kda_scan_roofline_pct)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = load("configs", "kimi-linear-48b-a3b")
TRAFFIC = load("traffic", "b2-s4096")
# the catalog's ``config`` of Kimi-Linear-48B-A3B-Instruct, every key
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_published_widths_are_whole_and_the_cut_is_written_down():
    reduced = ["num_hidden_layers", "num_experts", "vocab_size",
               "linear_attn_config"]
    assert CONFIG["reduced"] == reduced
    # every key that is not reduced holds the published value
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    # the cut: depth, experts held, vocabulary, and the two layer lists
    assert {k: CONFIG[k] for k in reduced[:3]} == {
        "num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
    linear, published = CONFIG["linear_attn_config"], PUBLISHED[
        "linear_attn_config"]
    assert linear == {**published, "kda_layers": [1, 2, 3, 5],
                      "full_attn_layers": [4]}
    # the model's first five layers as they stand
    assert linear["kda_layers"] == [
        i for i in published["kda_layers"] if i <= 5]
    assert linear["full_attn_layers"] == [
        i for i in published["full_attn_layers"] if i <= 5]
    assert CONFIG["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "linear_attn_config": {
            "full_attn_layers": published["full_attn_layers"],
            "kda_layers": published["kda_layers"]}}
    assert sorted(published["kda_layers"] + published["full_attn_layers"]
                  ) == list(range(1, 28))
    deployment = CONFIG["deployment"]
    assert deployment["chips_that_share_each_layer"] == 32
    assert deployment["router_width"] == 256
    assert deployment["expert_offset"] == 0
    assert CONFIG["num_experts"] * 32 == 256
    assert CONFIG["vocab_size"] * 8 == 163840  # the guide's floor
    assert TRAFFIC["per_chip_batch"] == 2 and TRAFFIC["seq_len"] == 4096
    assert TRAFFIC["mesh"] == {"data": 1}
    assumed = CONFIG["assumed"]
    assert (assumed["kda_chunk_size"], assumed["kda_gate_rank"]) == (64, 128)
    for key in ("recomputed", "scan_statistics_dtype", "selection_bias_std",
                "selection_bias_std_why", "weights", "kda",
                "sequence_length"):
        assert assumed[key]
    departures = " ".join(CONFIG["departures"])
    assert "no rotary" in departures and "one position at a time" in departures


def test_the_pattern_is_the_models_first_five_layers():
    z = kda_moe_lm._sizes(CONFIG)
    assert z["pattern"] == (("kda", "swiglu"), ("kda", "experts"),
                            ("kda", "experts"), ("mla", "experts"),
                            ("kda", "experts"))
    assert (z["kda_layers"], z["mla_layers"], z["dense_layers"],
            z["expert_layers"]) == (4, 1, 1, 4)
    with pytest.raises(ValueError, match="each of the 5 layers once"):
        kda_moe_lm._sizes({**CONFIG, "linear_attn_config": {
            **CONFIG["linear_attn_config"], "kda_layers": [1, 2, 3]}})
    with pytest.raises(ValueError, match="each of the 4 layers once"):
        kda_moe_lm._sizes({**CONFIG, "num_hidden_layers": 4})


def test_parameters_held():
    norms = 2 * 2304
    kda = (3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
           + 2304 * 32 + 3 * 4 * 4096 + 32 + 4096 + 4096 + 128)
    assert kda == 39_518_368
    mla = (2304 * 32 * 192 + 2304 * (512 + 64) + 512
           + 512 * 32 * (128 + 128) + 32 * 128 * 2304)
    assert mla == 29_114_880
    dense, expert = 3 * 2304 * 9216, 3 * 2304 * 1024
    assert (dense, expert) == (63_700_992, 7_077_888)
    sparse = 8 * expert + 2304 * 256 + 256 + expert  # + router, bias, shared
    held = ((kda + dense + norms) + 3 * (kda + sparse + norms)
            + (mla + sparse + norms) + 2 * 20480 * 2304 + 2304)
    assert CONFIG["parameters"] == held == 602_450_816
    # the 27 published layers and the whole vocabulary: the row's "48B"
    whole_sparse = 256 * expert + 2304 * 256 + 256 + expert
    whole = ((kda + dense + norms) + 19 * (kda + whole_sparse + norms)
             + 7 * (mla + whole_sparse + norms) + 2 * 163840 * 2304 + 2304)
    assert whole / 1e9 == pytest.approx(49.12, abs=0.01)
    # the routed experts are 37.6% of what is held
    assert 4 * 8 * expert / held == pytest.approx(0.376, abs=0.001)


def test_token_flops():
    # by hand: 6 a parameter of the four delta layers' projections,
    # low-rank pairs and beta, latent attention's four matrices, the dense
    # SwiGLU, four routers and shared experts, 0.25 held experts a token in
    # four layers, the head; causal attention at half, QK^T at 192 and PV
    # at 128, 32 heads, one layer; the delta rule
    delta = 3 * 32 * (5 * 64 * 128 + 6 * 128 * 128)
    assert delta == 13_369_344
    kda = 4 * 2304 * 4096 + 2 * 128 * (2304 + 4096) + 2304 * 32
    mla = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304)
    hand = (6 * (4 * kda + mla + 3 * 2304 * 9216
                 + 4 * (3 * 2304 * 1024 + 2304 * 256)
                 + 4 * 8 * 8 / 256 * 3 * 2304 * 1024 + 2304 * 20480)
            + 3 * 0.5 * 32 * 2 * 4096 * (192 + 128)
            + 4 * delta)
    got = kda_moe_lm.required_flops_per_item(CONFIG, TRAFFIC)
    assert got == hand
    assert got / 1e9 == pytest.approx(2.193, abs=0.001)
    assert got * 8192 / 1e12 == pytest.approx(17.96, abs=0.01)
    # the delta layers are 46% of it, their scans 2.4%, the flash kernel 6%
    assert (6 * 4 * kda + 4 * delta) / got == pytest.approx(0.456, abs=0.01)
    assert 4 * delta / got == pytest.approx(0.024, abs=0.001)
    assert (3 * 0.5 * 32 * 2 * 4096 * 320) / got == pytest.approx(
        0.057, abs=0.002)


def test_kernel_work():
    work = kda_moe_lm.kernel_work(CONFIG, TRAFFIC)
    # the flash kernel: one layer, 2 rows, 32 heads of 192 / 128 at 4096
    forward = 0.5 * 2 * 4096 ** 2 * (192 + 128)
    assert work["flops"] == 2 * 32 * 3 * forward
    wide, narrow, stats = 4096 * 192 * 2, 4096 * 128 * 2, 4096 * 4
    assert work["bytes"] == 2 * 32 * (6 * wide + 6 * narrow + 3 * stats)
    # 2,048 expected held token-slots a layer, three products an expert
    assert work["grouped_flops"] == 4 * 2048 * 6 * 3 * 2304 * 1024
    product = 2048 * (2304 + 1024) + 8 * 2304 * 1024
    assert work["grouped_bytes"] == 4 * 3 * 3 * 2 * product
    # at 256 slots an expert the weights' bytes bind
    assert ((work["grouped_bytes"] / 819e9)
            / (work["grouped_flops"] / 197e12)) == pytest.approx(
                1.28, abs=0.01)
    # the delta rule: 32,768 token-layers; q, k, v, o in bfloat16, the
    # log-decay and beta in float32, and their gradients
    assert work["delta_flops"] == 4 * 8192 * 13_369_344
    inputs = 3 * 4096 * 2 + (4096 + 32) * 4
    assert work["delta_bytes"] == 4 * 8192 * (3 * inputs + 2 * 4096 * 2) == (
        4 * 8192 * 139_648)
    # the bytes bind, by about 2.5 to 1: 5.6 ms against 2.2 ms a step
    assert work["delta_bytes"] / 819e9 == pytest.approx(5.59e-3, abs=1e-5)
    assert (work["delta_bytes"] / 819e9) / (work["delta_flops"] / 197e12) == (
        pytest.approx(2.51, abs=0.01))


def _run(table, by_name, window_s=1.0):
    return {"instruction_table": table, "traced_steps": 2,
            "summary": {"chips": {0: {"by_name": by_name,
                                      "window_s": window_s}}},
            "kernel_work": {"delta_flops": 197e12 * 0.01,
                            "delta_bytes": 819e9 * 0.05},
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}}


def _table(**op_names):
    return {name: {"category": "fusion", "opcode": "fusion",
                   "op_name": op_name}
            for name, op_name in op_names.items()}


def test_the_readers_sum_forward_recomputed_and_backward():
    table = _table(
        a="jit(step)/jvp(T)/block_0/mixer/hvd_kda_scan/while/body/"
          "checkpoint/exp",
        b="jit(step)/transpose(jvp(T))/block_0/mixer/hvd_kda_scan/while/"
          "body/checkpoint/rematted_computation/dot_general",
        c="jit(step)/transpose(jvp(T))/block_0/mixer/hvd_kda_scan/while/"
          "body/checkpoint/while/body/mul",
        d="jit(step)/jvp(T)/block_0/mixer/hvd_kda/q_proj/dot_general",
        e="jit(step)/transpose(jvp(T))/block_0/mixer/hvd_kda/q_conv1d/mul",
        f="jit(step)/jvp(T)/block_3/attn/pallas_call",
        g="jit(step)/jvp(T)/block_0/mixer/hvd_kda_scanned/x")
    run = _run(table, {"a": 0.1, "b": 0.1, "c": 0.2, "d": 0.2, "e": 0.05,
                       "f": 0.25, "g": 0.05})
    assert kda_scan_pct.read(run) == pytest.approx(40.0)
    assert kda_pct.read(run) == pytest.approx(25.0)  # not the scan
    # the bytes bind: least time 0.05 s a step, two steps, 0.4 s under it
    assert kda_scan_roofline_pct.read(run) == pytest.approx(25.0)


def test_a_scope_the_executable_lacks_is_left_out_not_zero(capsys):
    """What the parent commit's program gives these readers: no
    instruction under either scope, so the line leaves the metrics out."""
    run = _run(_table(a="jit(step)/jvp(T)/block_0/attn/hvd_mla/dot"),
               {"a": 1.0})
    assert kda_pct.read(run) is None
    assert kda_scan_pct.read(run) is None
    assert kda_scan_roofline_pct.read(run) is None
    assert "hvd_kda_scan" in capsys.readouterr().err
    assert scope_time.pct(run, "hvd_mla") == pytest.approx(100.0)
    # a family that states no delta rule: nothing to divide
    table = _table(a="jit(step)/jvp(T)/mixer/hvd_kda_scan/exp")
    for work in ({"scan_flops": 1.0, "scan_bytes": 1.0}, None):
        run = {**_run(table, {"a": 1.0}), "kernel_work": work}
        assert kda_scan_roofline_pct.read(run) is None
        assert kda_scan_pct.read(run) == pytest.approx(100.0)
