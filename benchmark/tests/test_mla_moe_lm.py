"""The ``mla_moe_lm`` family's arithmetic against hand arithmetic, its
configuration against the catalog's published numbers, and the scope
readers on hand-made inputs."""

import json
import os

import pytest

from benchmark.families import mla_moe_lm
from benchmark.harness import scope_time
from benchmark.layer_metrics import (mla_pct, moe_experts_pct,
                                     moe_experts_roofline_pct)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = load("configs", "kanana-2-30b-a3b")
TRAFFIC = load("traffic", "b4-s4096")


def test_published_widths_are_whole_and_the_cut_is_written_down():
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "qk_head_dim": 192, "v_head_dim": 128, "kv_lora_rank": 512,
              "moe_intermediate_size": 768, "intermediate_size": 6144,
              "num_experts_per_tok": 6, "n_shared_experts": 2,
              "routed_scaling_factor": 2.448, "rope_theta": 1000000,
              "first_k_dense_replace": 1, "rms_norm_eps": 1e-6}
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert {k: CONFIG[k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032}
    deployment = CONFIG["deployment"]
    assert deployment["chips_that_share_each_layer"] == 8
    assert deployment["router_width"] == 128
    assert CONFIG["vocab_size"] * 8 == 128256
    assert TRAFFIC["per_chip_batch"] == 4 and TRAFFIC["seq_len"] == 4096
    assert TRAFFIC["mesh"] == {"data": 1}


def test_parameters_held():
    attention = (2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
                 + 32 * 128 * 2048)
    assert attention == 26_345_984
    dense = attention + 3 * 2048 * 6144 + 2 * 2048
    outside = (attention + 2048 * 128 + 128 + 3 * 2048 * 1536 + 2 * 2048)
    expert = 3 * 2048 * 768
    held = (dense + 4 * (outside + 16 * expert) + 2 * 16032 * 2048 + 2048)
    assert CONFIG["parameters"] == held == 575_955_968


def test_token_flops():
    # by hand, as ISSUE 27 reckons it: 6 a parameter of the five attention
    # modules' four matrices, the dense SwiGLU, four shared SwiGLUs and
    # routers, 0.75 held experts a token in four layers, the head; causal
    # attention at half, QK^T at 192 and PV at 128, 32 heads
    attention = 26_345_984 - 512
    hand = (6 * (5 * attention + 3 * 2048 * 6144
                 + 4 * (3 * 2048 * 1536 + 2048 * 128)
                 + 4 * 6 * 16 / 128 * 3 * 2048 * 768 + 2048 * 16032)
            + 5 * 3 * 0.5 * (2 * 4096 * 192 + 2 * 4096 * 128) * 32)
    got = mla_moe_lm.required_flops_per_item(CONFIG, TRAFFIC)
    assert got == hand
    assert got / 1e9 == pytest.approx(2.161, abs=0.001)
    assert got * 16384 / 1e12 == pytest.approx(35.4, abs=0.05)


def test_kernel_work():
    work = mla_moe_lm.kernel_work(CONFIG, TRAFFIC)
    calls = 5 * 4 * 32
    forward = 0.5 * (2 * 4096 ** 2 * 192 + 2 * 4096 ** 2 * 128)
    assert work["flops"] == calls * 3 * forward
    wide, narrow, stats = 4096 * 192 * 2, 4096 * 128 * 2, 4096 * 4
    assert work["bytes"] == calls * (6 * wide + 6 * narrow + 3 * stats)
    # the kernel is 29% of what a step requires
    step = mla_moe_lm.required_flops_per_item(CONFIG, TRAFFIC) * 16384
    assert work["flops"] / step == pytest.approx(0.291, abs=0.001)
    # 12,288 expected held token-slots a layer, 6 * 3 * 2048 * 768 each
    assert work["grouped_flops"] == 4 * 12288 * 6 * 3 * 2048 * 768
    assert work["grouped_flops"] / step == pytest.approx(0.039, abs=0.001)
    product = 12288 * (2048 + 768) + 16 * 2048 * 768
    assert work["grouped_bytes"] == 4 * 3 * 3 * 2 * product
    # at 768 slots an expert the FLOPs still bind, by about four to three
    assert (work["grouped_flops"] / 197e12
            > 1.3 * work["grouped_bytes"] / 819e9)


def _run(table, by_name, window_s=1.0):
    return {"instruction_table": table, "traced_steps": 2,
            "summary": {"chips": {0: {"by_name": by_name,
                                      "window_s": window_s}}},
            "kernel_work": {"grouped_flops": 197e12 * 0.05,
                            "grouped_bytes": 819e9 * 0.01},
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}}


def _table(**op_names):
    return {name: {"category": "fusion", "opcode": "fusion",
                   "op_name": op_name}
            for name, op_name in op_names.items()}


def test_scope_readers_sum_forward_recomputed_and_backward():
    table = _table(
        a="jit(step)/jvp(T)/block_1/experts/hvd_moe_experts/jit(gmm)/x",
        b="jit(step)/transpose(jvp(T))/checkpoint/rematted_computation/"
          "block_1/experts/hvd_moe_experts/mul",
        c="jit(step)/transpose(jvp(T))/block_1/experts/hvd_moe_experts/y",
        d="jit(step)/jvp(T)/block_1/attn/hvd_mla/dot_general",
        e="jit(step)/jvp(T)/block_1/attn/pallas_call",
        f="jit(step)/jvp(T)/block_1/experts/hvd_moe_experts_not/z")
    run = _run(table, {"a": 0.1, "b": 0.1, "c": 0.2, "d": 0.3, "e": 0.25,
                       "f": 0.05})
    assert moe_experts_pct.read(run) == pytest.approx(40.0)
    assert mla_pct.read(run) == pytest.approx(30.0)  # not the kernel
    # least time 0.05 s a step, two steps, 0.4 s under the scope
    assert moe_experts_roofline_pct.read(run) == pytest.approx(25.0)


def test_a_scope_the_executable_lacks_is_left_out_not_zero(capsys):
    run = _run(_table(a="jit(step)/jvp(T)/block_0/Dense_0/dot"), {"a": 1.0})
    assert scope_time.pct(run, "hvd_moe_route") is None
    assert moe_experts_roofline_pct.read(run) is None
    assert "hvd_moe_route" in capsys.readouterr().err
    # a family that states no grouped work: nothing to divide
    run["kernel_work"] = {"flops": 1.0, "bytes": 1.0}
    assert moe_experts_roofline_pct.read(run) is None
    run["kernel_work"] = None
    assert moe_experts_roofline_pct.read(run) is None


def test_flash_readers_take_the_attention_calls_and_no_other_kernel():
    """Both directions of ``attn/pallas_call``; not the grouped products'
    Pallas calls, which ``flash_kernel_pct`` could not tell apart."""
    from benchmark.layer_metrics import mla_flash_pct, mla_flash_roofline_pct

    table = _table(
        a="jit(step)/jvp(T)/block_1/attn/pallas_call",
        b="jit(step)/transpose(jvp(T))/block_1/attn/pallas_call",
        c="jit(step)/jvp(T)/block_1/experts/hvd_moe_experts/jit(gmm)/"
          "pallas_call",
        d="jit(step)/jvp(T)/block_1/attn/hvd_mla/dot_general")
    run = _run(table, {"a": 0.1, "b": 0.15, "c": 0.3, "d": 0.2})
    run["kernel_work"].update(flops=197e12 * 0.05, bytes=819e9 * 0.02)
    assert mla_flash_pct.read(run) == pytest.approx(25.0)
    # least time 0.05 s a step, two steps, 0.25 s in the kernel
    assert mla_flash_roofline_pct.read(run) == pytest.approx(40.0)
    run["kernel_work"] = {"grouped_flops": 1.0, "grouped_bytes": 1.0}
    assert mla_flash_roofline_pct.read(run) is None
    del table["a"], table["b"]
    assert mla_flash_pct.read(run) is None


def test_the_worst_chip_is_reported():
    table = _table(a="jit(step)/jvp(T)/hvd_mla/dot")
    run = _run(table, {"a": 0.1})
    run["summary"]["chips"][1] = {"by_name": {"a": 0.3}, "window_s": 1.0}
    assert mla_pct.read(run) == pytest.approx(30.0)
