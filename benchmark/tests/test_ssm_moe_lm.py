"""The ``ssm_moe_lm`` family's arithmetic against hand arithmetic, its
configuration against the catalog's published numbers, and the three
state-space readers on hand-made inputs."""

import json
import os

import pytest

from benchmark.families import ssm_moe_lm
from benchmark.harness import scope_time
from benchmark.layer_metrics import (ssm_pct, ssm_scan_pct,
                                     ssm_scan_roofline_pct)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = load("configs", "nemotron-3-nano-30b-a3b")
TRAFFIC = load("traffic", "b2-s4096")


def test_published_widths_are_whole_and_the_cut_is_written_down():
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "conv_kernel": 4, "chunk_size": 128, "expand": 2,
              "num_attention_heads": 32, "num_key_value_heads": 2,
              "head_dim": 128, "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "intermediate_size": 1856, "num_experts_per_tok": 6,
              "n_shared_experts": 1, "routed_scaling_factor": 2.5,
              "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
              "time_step_min": 0.001, "time_step_max": 0.1,
              "time_step_floor": 1e-4, "mlp_hidden_act": "relu2",
              "model_type": "nemotron_h", "max_position_embeddings": 262144}
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    published = CONFIG["published"]
    assert published["num_hidden_layers"] == 52 == len(
        published["hybrid_override_pattern"])
    assert (published["n_routed_experts"], published["vocab_size"]) == (
        128, 131072)
    # the cut: the source's first nine layers, 4 : 4 : 1 for 23 : 23 : 6
    pattern = CONFIG["hybrid_override_pattern"]
    assert pattern == published["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert [published["hybrid_override_pattern"].count(k)
            for k in "ME*"] == [23, 23, 6]
    assert {k: CONFIG[k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 9, "hybrid_override_pattern": pattern,
        "n_routed_experts": 8, "vocab_size": 16384}
    deployment = CONFIG["deployment"]
    assert deployment["chips_that_share_each_layer"] == 16
    assert deployment["router_width"] == 128
    assert deployment["expert_offset"] == 0
    assert CONFIG["n_routed_experts"] * 16 == 128
    assert CONFIG["vocab_size"] * 8 == 131072  # the guide's floor
    assert TRAFFIC["per_chip_batch"] == 2 and TRAFFIC["seq_len"] == 4096
    assert TRAFFIC["mesh"] == {"data": 1}
    for key in ("recomputed", "scan_statistics_dtype", "selection_bias_std",
                "weights"):
        assert CONFIG["assumed"][key]
    departures = " ".join(CONFIG["departures"])
    assert "no position embedding" in departures
    assert "broadcast" in departures


def test_parameters_held():
    norm = 2688
    mamba = (2688 * (4096 + 6144 + 64) + 6144 * 4 + 6144 + 3 * 64 + 4096
             + 4096 * 2688 + norm)
    assert mamba == 38_744_896
    attention = 2688 * 128 * (32 + 2 + 2) + 32 * 128 * 2688
    assert attention == 23_396_352
    expert = 2 * 2688 * 1856
    assert expert == 9_977_856
    experts = (2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * expert + norm)
    held = (4 * mamba + 4 * experts + attention + norm
            + 2 * 16384 * 2688 + norm)
    assert CONFIG["parameters"] == held == 666_963_456
    # the 52 published layers and the whole vocabulary: the row's "31.6B"
    whole = (23 * mamba + 6 * (attention + norm)
             + 23 * (experts + 120 * expert) + 2 * 131072 * 2688 + norm)
    assert whole / 1e9 == pytest.approx(31.58, abs=0.01)


def test_token_flops():
    # by hand: 6 a parameter of the four state-space layers' two
    # projections, attention's four matrices, four routers and shared
    # experts, 0.375 held experts a token in four layers, the head; causal
    # attention at half, QK^T and PV at 128, 32 heads, one layer; the scan
    scan = 3 * (8 * 128 * 128 + 64 * (128 * 64 + 4 * 64 * 128))
    assert scan == 8_257_536
    hand = (6 * (4 * (2688 * 10304 + 4096 * 2688)
                 + 2688 * 128 * 36 + 4096 * 2688
                 + 4 * (2 * 2688 * 3712 + 2688 * 128)
                 + 4 * 6 * 8 / 128 * 2 * 2688 * 1856 + 2688 * 16384)
            + 3 * 0.5 * 32 * (2 * 4096 * 128 + 2 * 4096 * 128)
            + 4 * scan)
    got = ssm_moe_lm.required_flops_per_item(CONFIG, TRAFFIC)
    assert got == hand
    assert got / 1e9 == pytest.approx(2.044, abs=0.001)
    assert got * 8192 / 1e12 == pytest.approx(16.75, abs=0.01)
    # the state-space layers are more than half of it, their scan 1.6%
    mamba = 6 * 4 * (2688 * 10304 + 4096 * 2688) + 4 * scan
    assert mamba / got == pytest.approx(0.47, abs=0.01)
    assert 4 * scan / got == pytest.approx(0.016, abs=0.001)


def test_kernel_work():
    work = ssm_moe_lm.kernel_work(CONFIG, TRAFFIC)
    # the flash kernel: one layer, 2 rows, 32 query heads of 128 at 4096
    forward = 0.5 * 4 * 4096 ** 2 * 128
    assert work["flops"] == 2 * 32 * 3 * forward
    tensor, stats = 4096 * 128 * 2, 4096 * 4
    assert work["bytes"] == 2 * (32 * (6 * tensor + 3 * stats)
                                 + 2 * 6 * tensor)
    # 3,072 expected held token-slots a layer, two products an expert
    assert work["grouped_flops"] == 4 * 3072 * 6 * 2 * 2688 * 1856
    product = 3072 * (2688 + 1856) + 8 * 2688 * 1856
    assert work["grouped_bytes"] == 4 * 2 * 3 * 2 * product
    # at 384 slots an expert the FLOPs bind, by about six to five
    assert (1.1 < (work["grouped_flops"] / 197e12)
            / (work["grouped_bytes"] / 819e9) < 1.3)
    # the scan: 32,768 token-layers; u, B, C, the step and o and their
    # gradients in bfloat16
    assert work["scan_flops"] == 4 * 8192 * 8_257_536
    inputs = 4096 + 2 * 8 * 128 + 64
    assert work["scan_bytes"] == 4 * 8192 * 2 * (
        2 * (inputs + 4096) + inputs) == 4 * 8192 * 53_632
    # the bytes bind, by about 1.6 to 1
    assert (work["scan_bytes"] / 819e9) / (work["scan_flops"] / 197e12) == (
        pytest.approx(1.56, abs=0.01))


def test_a_pattern_that_does_not_match_its_depth_is_refused():
    with pytest.raises(ValueError, match="names 9 layers"):
        ssm_moe_lm._sizes({**CONFIG, "num_hidden_layers": 7})
    with pytest.raises(ValueError, match="M, E and \\*"):
        ssm_moe_lm._sizes({**CONFIG, "hybrid_override_pattern": "MEMEM-EME"})


def _run(table, by_name, window_s=1.0):
    return {"instruction_table": table, "traced_steps": 2,
            "summary": {"chips": {0: {"by_name": by_name,
                                      "window_s": window_s}}},
            "kernel_work": {"scan_flops": 197e12 * 0.01,
                            "scan_bytes": 819e9 * 0.05},
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}}


def _table(**op_names):
    return {name: {"category": "fusion", "opcode": "fusion",
                   "op_name": op_name}
            for name, op_name in op_names.items()}


def test_the_readers_sum_forward_recomputed_and_backward():
    table = _table(
        a="jit(step)/jvp(T)/block_0/mixer/hvd_ssm_scan/checkpoint/exp",
        b="jit(step)/transpose(jvp(T))/block_0/mixer/hvd_ssm_scan/"
          "checkpoint/rematted_computation/dot_general",
        c="jit(step)/transpose(jvp(T))/block_0/mixer/hvd_ssm_scan/"
          "checkpoint/while/body/mul",
        d="jit(step)/jvp(T)/block_0/mixer/hvd_ssm/in_proj/dot_general",
        e="jit(step)/transpose(jvp(T))/block_0/mixer/hvd_ssm/conv1d/mul",
        f="jit(step)/jvp(T)/block_5/attn/pallas_call",
        g="jit(step)/jvp(T)/block_0/mixer/hvd_ssm_scanned/x")
    run = _run(table, {"a": 0.1, "b": 0.1, "c": 0.2, "d": 0.2, "e": 0.05,
                       "f": 0.25, "g": 0.05})
    assert ssm_scan_pct.read(run) == pytest.approx(40.0)
    assert ssm_pct.read(run) == pytest.approx(25.0)  # not the scan
    # the bytes bind: least time 0.05 s a step, two steps, 0.4 s under it
    assert ssm_scan_roofline_pct.read(run) == pytest.approx(25.0)


def test_a_scope_the_executable_lacks_is_left_out_not_zero(capsys):
    """What the parent commit's program gives these readers: no
    instruction under either scope, so the line leaves the metrics out."""
    run = _run(_table(a="jit(step)/jvp(T)/block_0/attn/hvd_mla/dot"),
               {"a": 1.0})
    assert ssm_pct.read(run) is None
    assert ssm_scan_pct.read(run) is None
    assert ssm_scan_roofline_pct.read(run) is None
    assert "hvd_ssm_scan" in capsys.readouterr().err
    assert scope_time.pct(run, "hvd_mla") == pytest.approx(100.0)
    # a family that states no scan: nothing to divide
    table = _table(a="jit(step)/jvp(T)/mixer/hvd_ssm_scan/exp")
    for work in ({"flops": 1.0, "bytes": 1.0}, None):
        run = {**_run(table, {"a": 1.0}), "kernel_work": work}
        assert ssm_scan_roofline_pct.read(run) is None
        assert ssm_scan_pct.read(run) == pytest.approx(100.0)
