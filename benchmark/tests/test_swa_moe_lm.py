"""The ``swa_moe_lm`` family's arithmetic against hand arithmetic, its
configuration against the catalog's published numbers, and the six
attention readers on hand-made inputs."""

import json
import os

import pytest

from benchmark.families import swa_moe_lm
from benchmark.harness import scope_time
from benchmark.layer_metrics import (attn_pct, full_flash_pct,
                                     full_flash_roofline_pct,
                                     window_flash_pct,
                                     window_flash_roofline_pct,
                                     window_masked_block_pct)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = load("configs", "laguna-xs.2")
TRAFFIC = load("traffic", "b1-s8192")
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# the catalog's ``config`` of Laguna-XS.2, every key
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}


def test_published_widths_are_whole_and_the_cut_is_written_down():
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    # every key that is not reduced holds the published value
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    # the cut: the model's first eight layers as they stand, the experts
    # held, the vocabulary
    assert CONFIG["num_hidden_layers"] == 8
    for key in reduced[1:4]:
        assert CONFIG[key] == PUBLISHED[key][:8], key
    assert (CONFIG["num_experts"], CONFIG["vocab_size"]) == (8, 12544)
    assert CONFIG["published"] == {key: PUBLISHED[key] for key in reduced}
    deployment = CONFIG["deployment"]
    assert deployment["chips_that_share_each_layer"] == 32
    assert deployment["router_width"] == 256
    assert deployment["expert_offset"] == 0
    assert CONFIG["num_experts"] * 32 == 256
    assert CONFIG["vocab_size"] * 8 == 100352  # the guide's floor
    assert TRAFFIC["per_chip_batch"] == 1 and TRAFFIC["seq_len"] == 8192
    assert TRAFFIC["mesh"] == {"data": 1}
    assumed = CONFIG["assumed"]
    for key in ("gate", "router", "hidden_act", "qk_norm", "rotary",
                "recomputed", "selection_bias_std", "weights",
                "sequence_length"):
        assert assumed[key]
    assert set(CONFIG["reduced_how"]) == set(reduced) | {"fit"}
    assert "broadcast" in " ".join(CONFIG["departures"])


def test_the_pattern_is_the_models_first_eight_layers():
    z = swa_moe_lm._sizes(CONFIG)
    assert z["pattern"] == (
        ("full", "swiglu"), ("sliding", "experts"), ("sliding", "experts"),
        ("sliding", "experts"), ("full", "experts"), ("sliding", "experts"),
        ("sliding", "experts"), ("sliding", "experts"))
    assert z["heads"] == {"full": 48, "sliding": 64}
    assert (z["full_layers"], z["sliding_layers"], z["dense_layers"],
            z["expert_layers"]) == (2, 6, 1, 7)
    with pytest.raises(ValueError, match="layers of 7"):
        swa_moe_lm._sizes({**CONFIG, "num_hidden_layers": 7})
    with pytest.raises(ValueError, match="a kind has one size"):
        swa_moe_lm._sizes({**CONFIG, "num_attention_heads_per_layer": [
            48, 64, 64, 64, 48, 64, 64, 32]})


def test_the_kinds_are_read_from_rope_parameters():
    program, reference = swa_moe_lm.attention_kinds(CONFIG)
    yarn = {"factor": 64, "original_max_position_embeddings": 4096,
            "beta_fast": 64, "beta_slow": 1,
            "attention_factor": 1.4158883083359672}
    assert program == {
        "full": dict(kind="full", num_heads=48, head_dim=128,
                     num_kv_heads=8, window=None, rope_theta=500000.0,
                     rotary_dim=64, yarn=yarn, gate=True),
        "sliding": dict(kind="sliding", num_heads=64, head_dim=128,
                        num_kv_heads=8, window=512, rope_theta=10000.0,
                        rotary_dim=128, yarn=None, gate=True)}
    assert reference == {
        "full": {"sliding_window": None, "rope_theta": 500000,
                 "rotary_dim": 64, "yarn": yarn},
        "sliding": {"sliding_window": 512, "rope_theta": 10000,
                    "rotary_dim": 128, "yarn": None}}


def test_parameters_held():
    norms = 2 * 2048
    full = 2048 * 128 * (2 * 48 + 2 * 8) + 2048 * 48
    sliding = 2048 * 128 * (2 * 64 + 2 * 8) + 2048 * 64
    assert (full, sliding) == (29_458_432, 37_879_808)
    dense, expert = 3 * 2048 * 8192, 3 * 2048 * 512
    assert (dense, expert) == (50_331_648, 3_145_728)
    sparse = 8 * expert + 2048 * 256 + 256 + expert  # + router, bias, shared
    held = ((full + dense + norms) + (full + sparse + norms)
            + 6 * (sliding + sparse + norms) + 2 * 12544 * 2048 + 2048)
    assert CONFIG["parameters"] == held == swa_moe_lm.parameters(
        CONFIG) == 589_795_072
    assert held * 16 / 2 ** 30 == pytest.approx(8.79, abs=0.01)
    # the 40 published layers and the whole vocabulary: the row's "33.4B"
    whole_sparse = 256 * expert + 2048 * 256 + 256 + expert
    whole = ((full + dense + norms) + 9 * (full + whole_sparse + norms)
             + 30 * (sliding + whole_sparse + norms)
             + 2 * 100352 * 2048 + 2048)
    assert whole / 1e9 == pytest.approx(33.4426, abs=0.0001)
    # a gate an element would add 629 M and not be called 33.4 B
    assert 2048 * 128 * (10 * 48 + 30 * 64) / 1e6 == pytest.approx(
        629, abs=1)


def test_token_flops():
    # by hand: 6 a parameter of the two full and six sliding layers'
    # projections and gates, the dense SwiGLU, seven routers and shared
    # experts, 0.25 held experts a token in seven layers, the head; the
    # scores at the pairs the mask leaves, a token
    full = 2048 * 128 * (2 * 48 + 16) + 2048 * 48
    sliding = 2048 * 128 * (2 * 64 + 16) + 2048 * 64
    s, w = 8192, 512
    full_pairs, window_pairs = s * (s + 1) // 2, w * s - w * (w - 1) // 2
    assert window_pairs == 4_063_488
    scores = (2 * 48 * full_pairs + 6 * 64 * window_pairs) * 3 * 2 * 256 / s
    hand = (6 * (2 * full + 6 * sliding + 3 * 2048 * 8192
                 + 7 * (3 * 2048 * 512 + 2048 * 256)
                 + 7 * 8 * 8 / 256 * 3 * 2048 * 512 + 2048 * 12544)
            + scores)
    got = swa_moe_lm.required_flops_per_item(CONFIG, TRAFFIC)
    assert got == pytest.approx(hand, rel=1e-12)
    assert got / 1e9 == pytest.approx(3.257, abs=0.001)
    assert got * 8192 / 1e12 == pytest.approx(26.68, abs=0.01)
    # attention is 80% of the step's required work (projections 1.72,
    # full scores 0.60, sliding scores 0.29 GFLOP a token), the sliding
    # layers 51%
    projections = 6 * (2 * full + 6 * sliding)
    assert projections / 1e9 == pytest.approx(1.717, abs=0.001)
    assert 2 * 48 * full_pairs * 1536 / s / 1e9 == pytest.approx(
        0.604, abs=0.001)
    assert 6 * 64 * window_pairs * 1536 / s / 1e9 == pytest.approx(
        0.293, abs=0.001)
    assert (projections + scores) / got == pytest.approx(0.802, abs=0.002)
    assert (6 * 6 * sliding + 6 * 64 * window_pairs * 1536 / s) / got == (
        pytest.approx(0.509, abs=0.002))
    # without the window in the schedule a sliding layer would ask for
    # the full layers' half-square: 403 MFLOP a token in place of 49
    assert 64 * full_pairs * 1536 / s / 1e6 == pytest.approx(403, abs=1)
    assert 64 * window_pairs * 1536 / s / 1e6 == pytest.approx(48.8, abs=0.1)


def test_kernel_work():
    work = swa_moe_lm.kernel_work(CONFIG, TRAFFIC)
    s, w = 8192, 512
    # 2 full layers of 48 heads, 6 sliding layers of 64, one batch row
    assert work["full_flops"] == 2 * 48 * 3 * 2 * (s * (s + 1) // 2) * 256
    assert work["window_flops"] == (
        6 * 64 * 3 * 2 * (w * s - w * (w - 1) // 2) * 256)
    tensor, stats = s * 128 * 2, s * 4
    call = 12 * tensor + 3 * stats  # k and v a QUERY head
    assert work["full_bytes"] == 2 * 48 * call
    assert work["window_bytes"] == 6 * 64 * call
    assert work["window_shape"] == [8192, 512]
    # the full layers are bound by FLOPs (25.1 ms against 3.0), the
    # sliding layers nearly evenly (12.2 ms of FLOPs, 11.8 of bytes)
    assert work["full_flops"] / 197e12 == pytest.approx(25.1e-3, abs=1e-4)
    assert work["window_flops"] / 197e12 == pytest.approx(12.17e-3, abs=1e-5)
    assert work["window_bytes"] / 819e9 == pytest.approx(11.85e-3, abs=1e-5)
    # 2,048 expected held token-slots a layer, three products an expert
    assert work["grouped_flops"] == 7 * 2048 * 6 * 3 * 2048 * 512
    product = 2048 * (2048 + 512) + 8 * 2048 * 512
    assert work["grouped_bytes"] == 7 * 3 * 3 * 2 * product
    assert "flops" not in work  # nothing for the latent-attention readers


def _run(table, by_name, window_s=1.0):
    return {"instruction_table": table, "traced_steps": 2,
            "summary": {"chips": {0: {"by_name": by_name,
                                      "window_s": window_s}}},
            "kernel_work": {"full_flops": 197e12 * 0.05,
                            "full_bytes": 819e9 * 0.01,
                            "window_flops": 197e12 * 0.01,
                            "window_bytes": 819e9 * 0.02,
                            "window_shape": [8192, 512]},
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}}


def _table(**op_names):
    return {name: {"category": "fusion", "opcode": "fusion",
                   "op_name": op_name}
            for name, op_name in op_names.items()}


def test_the_readers_tell_the_kinds_and_the_rest_apart():
    table = _table(
        a="jit(step)/jvp(T)/block_0/attn/hvd_attn_full/attn/pallas_call",
        b="jit(step)/transpose(jvp(T))/block_0/attn/hvd_attn_full/attn/"
          "pallas_call",
        c="jit(step)/jvp(T)/block_1/attn/hvd_attn_window/attn/pallas_call",
        d="jit(step)/transpose(jvp(T))/block_1/attn/hvd_attn_window/attn/"
          "pallas_call",
        e="jit(step)/jvp(T)/block_1/attn/hvd_attn/query/dot_general",
        f="jit(step)/transpose(jvp(T))/block_0/attn/hvd_attn/gate/mul",
        g="jit(step)/jvp(T)/block_1/experts/hvd_moe_experts/pallas_call",
        h="jit(step)/jvp(T)/block_1/attn/hvd_attn_windowed/x")
    run = _run(table, {"a": 0.1, "b": 0.15, "c": 0.04, "d": 0.06, "e": 0.2,
                       "f": 0.1, "g": 0.3, "h": 0.05})
    assert full_flash_pct.read(run) == pytest.approx(25.0)
    assert window_flash_pct.read(run) == pytest.approx(10.0)
    assert attn_pct.read(run) == pytest.approx(30.0)  # neither kernel
    # full: the FLOPs bind, 0.05 s a step, two steps, 0.25 s under it
    assert full_flash_roofline_pct.read(run) == pytest.approx(40.0)
    # window: the bytes bind, 0.02 s a step, two steps, 0.1 s under it
    assert window_flash_roofline_pct.read(run) == pytest.approx(40.0)
    # the accepted readers' name for the flash kernel still finds all four
    assert scope_time.pct(run, "attn/pallas_call") == pytest.approx(35.0)


def test_the_block_schedules_share_comes_from_the_programs_own_blocks():
    from horovod_tpu.ops import flash_attention as fa

    run = _run({}, {})
    counts = [fa.block_schedule(8192, 8192, *pair, window=512)
              for pair in fa.WINDOW_BLOCKS]
    crossed = sum(c["diagonal"] for c in counts)
    ran = crossed + sum(c["interior"] for c in counts)
    assert window_masked_block_pct.read(run) == pytest.approx(
        100.0 * crossed / ran)
    assert 0 < window_masked_block_pct.read(run) <= 100
    assert all(c["skipped"] > 6 * (c["diagonal"] + c["interior"])
               for c in counts)


def test_what_the_parent_lacks_is_left_out_not_zero(capsys, monkeypatch):
    """What the parent commit's program gives these readers: no
    instruction under any of the three scopes and no windowed kernel, so
    the line leaves the metrics out."""
    from horovod_tpu.ops import flash_attention as fa

    run = _run(_table(a="jit(step)/jvp(T)/block_0/attn/pallas_call"),
               {"a": 1.0})
    for reader in (attn_pct, full_flash_pct, window_flash_pct,
                   full_flash_roofline_pct, window_flash_roofline_pct):
        assert reader.read(run) is None
    assert "hvd_attn_window" in capsys.readouterr().err
    monkeypatch.delattr(fa, "WINDOW_BLOCKS")
    assert window_masked_block_pct.read(run) is None
    # a family that states no attention of two kinds: nothing to divide
    table = _table(a="jit(step)/jvp(T)/attn/hvd_attn_full/attn/pallas_call")
    for work in ({"flops": 1.0, "bytes": 1.0}, None):
        run = {**_run(table, {"a": 1.0}), "kernel_work": work}
        assert full_flash_roofline_pct.read(run) is None
        assert window_masked_block_pct.read(run) is None
        assert full_flash_pct.read(run) == pytest.approx(100.0)
