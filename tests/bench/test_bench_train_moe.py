"""The Moonlight-16B-A3B training cell at a tiny size on the CPU, its kernel
counts and readers, and the one definition of the model."""

import copy
import time

import pytest

CELL = "train.moonlight_16b_a3b.fr4_moe"
CONFIG = "moonlight_16b_a3b"

# Every mechanism at a tiny size.  bfloat16 rounds these widths' gradients
# more coarsely than the published ones, so the comparison keeps limits of
# its own, set from this size's readings (CPU, seeds 1 to 6): sound
# grad/mean-grad/update gaps up to 6.5e-3/1.3e-3/1.4e-3, the float8
# control's from 7.4e-3/2.0e-3/3.8e-3, half of the batch left out from
# 8.8e-2/2.6e-2/0.25.  The loss is not compared, as in the cell.  In float32
# the program and the reference agree to 1e-6 on every number.
TINY_CONFIG = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "moe_intermediate_size": 64,
    "n_routed_experts": 4, "router_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "vocab_size": 1024,
}
TINY_LIMITS = {"grad_gap": 7e-3, "grad_mean_gap": 1.65e-3, "update_gap": 2.5e-3}


def tiny_cell(seed: int = 3):
    import harness
    import jax

    config = harness.config_of(CONFIG)
    config.update(copy.deepcopy(TINY_CONFIG))
    config["training"]["seq_len"] = 64
    traffic = harness.traffic_of("fr4_moe")
    traffic["limits"] = dict(TINY_LIMITS)
    return harness.Cell(name=CELL, config=config, traffic=traffic, seed=seed, chips=1,
                        devices=jax.devices(), config_module=harness.config_module(CONFIG))


def test_sound_run_line():
    import harness

    line = harness.run_cell(tiny_cell(), seconds=1.0, trace=False, bm=harness.benchmark(),
                            t_process=time.perf_counter(), log=lambda s: None)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_control_and_half_batch_are_not_correct():
    import harness
    from repro.obs import default_registry

    cell = tiny_cell()
    drv = harness.driver_of(cell.traffic)
    pairs = default_registry().value("moe_local_pairs")
    st = drv.setup(cell, 1.0, log=lambda s: None)
    drv.window(st, 1.0, harness.Tracer(False))
    assert default_registry().value("moe_dropped_pairs") == 0
    assert default_registry().value("moe_local_pairs") > pairs
    got = drv.readings(st)
    limits = cell.traffic["limits"]
    assert all(got["sound"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got
    assert any(got["half_batch"][k] > v for k, v in limits.items()), got


def test_preset_is_the_benchmark_model():
    """The registry's moonlight-16b-a3b and the benchmark's configuration
    agree on every published key the configuration does not cut."""
    import harness
    from repro.models.registry import get_config

    c = harness.config_of(CONFIG)
    uncut = {**c, "num_hidden_layers": 27, "n_routed_experts": c["router_experts"],
             "vocab_size": 163840}
    bench = harness.config_module(CONFIG).model_config(uncut)
    preset = get_config("moonlight-16b-a3b")
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert bench.moe == preset.moe.__class__(**{**preset.moe.__dict__, "experts_held": 64})
    for field in ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff", "lead",
                  "scan_unit", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_eps", "tie_embeddings"):
        assert getattr(bench, field) == getattr(preset, field), field


def test_cut_keeps_the_guides_floors():
    import harness

    c = harness.config_of(CONFIG)
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["n_routed_experts"] >= 8 and c["router_experts"] == 64
    assert c["vocab_size"] * 8 >= 163840


# A forward call's HLO text as the trace names it (operands shortened).
RAGGED = ("%gmm.3 = bf16[6144,1408]{1,0} custom-call(s32[] %a, s32[9]{0} %b, "
          "bf16[6144,2048]{1,0} %x, bf16[8,2048,1408]{2,1,0} %w), custom_call_target=\"tpu_custom_call\"")
RAGGED_DW = ("%tgmm.7 = bf16[8,1408,2048]{2,1,0} custom-call(s32[] %a, "
             "bf16[1408,6144]{1,0} %h, bf16[6144,2048]{1,0} %g)")
META = "%gather.1 = bf16[6144,2048]{1,0} gather(bf16[2048,2048]{1,0} %x, s32[6144]{0} %i)"
MLA = ("%_flash_attention_jit.11 = bf16[16,1024,128]{2,1,0} custom-call(bf16[16,1024,192]{2,1,0} %q, "
       "bf16[16,1024,192]{2,1,0} %k, bf16[16,1024,128]{2,1,0} %v)")
GQA = ("%_flash_attention_jit.3 = bf16[16,1024,128]{2,1,0} custom-call(bf16[16,1024,128]{2,1,0} %q, "
       "bf16[8,1024,128]{2,1,0} %k, bf16[8,1024,128]{2,1,0} %v)")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_expert_ffn_counts_follow_the_routed_pairs():
    import harness

    km = harness.kernel_counts("expert_ffn")
    assert km.matches(RAGGED) and km.matches(RAGGED_DW) and not km.matches(META)
    assert not km.matches(MLA)
    assert km.weights_shape(RAGGED) == [8, 2048, 1408]
    assert km.weights_shape(RAGGED_DW) == [8, 1408, 2048]
    # 768 routed pairs of 6144 static rows: 2 p K N FLOPs, weights once.
    assert km.flops(768, 2048, 1408) == 2.0 * 768 * 2048 * 1408
    assert km.bytes_moved(768, 8, 2048, 1408) == 2.0 * (8 * 2048 * 1408 + 768 * (2048 + 1408))
    t = km.least_seconds(RAGGED, 768, PEAKS)
    assert t == pytest.approx(km.bytes_moved(768, 8, 2048, 1408) / 819e9)


def test_mla_attention_counts_read_v_head_dim():
    import harness

    km = harness.kernel_counts("mla_attention")
    assert km.matches(MLA) and not km.matches(GQA) and not km.matches(RAGGED)
    sh = km.shapes(MLA)
    assert (sh["dk"], sh["dv"], sh["bh"], sh["t"]) == (192, 128, 16, 1024)
    assert km.flops(**sh) == 2.0 * 16 * (1024 * 1025 / 2) * (192 + 128)
    assert km.bytes_moved(**sh) == 2.0 * (16 * 1024 * 320 + 16 * 1024 * 320)


def _trace(names: dict, busy: float = 1.0) -> dict:
    return {"op_seconds": {n: s for n, (s, _) in names.items()},
            "op_calls": {n: c for n, (_, c) in names.items()}, "busy_s": busy}


def test_expert_ffn_readers():
    import harness
    from repro.obs import MetricsRegistry, set_default_registry

    reg = MetricsRegistry()
    old = set_default_registry(reg)
    try:
        c = harness.config_of(CONFIG)
        ctx = {"kernels": harness.kernel_counts, "peaks": PEAKS,
               "counters": {"config": c, "steps": 1},
               "trace": _trace({RAGGED: (0.01, 40), META: (0.001, 40), MLA: (0.02, 20)}, 0.5)}
        assert harness.reader_of("expert_ffn_roofline.moe").read(ctx) is None
        layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
        reg.counter("moe_steps").inc(2)
        # 768 pairs a group's layer: 4 groups compute each covered token twice.
        reg.counter("moe_local_pairs").inc(2 * 768 * layers * 4 // 2)
        km = harness.kernel_counts("expert_ffn")
        want = 100.0 * 40 * km.least_seconds(RAGGED, 768, PEAKS) / 0.01
        assert harness.reader_of("expert_ffn_roofline.moe").read(ctx) == pytest.approx(want)
        assert harness.reader_of("expert_ffn_share.moe").read(ctx) == pytest.approx(2.0)
        mla = harness.reader_of("mla_attention_roofline.moe").read(ctx)
        assert 0 < mla <= 100
    finally:
        set_default_registry(old)


def test_train_mfu_moe_counts_the_tokens_matmuls():
    import harness

    c = harness.config_of(CONFIG)
    m = harness.reader_of("train_mfu.moe")
    per_token = m.flops_per_token(c) / 6
    # MLA 13.76M a layer, the dense MLP once, then shared experts, router
    # and 6 x 8/64 of the held experts' FFN in each expert layer, the head.
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    moe = 2 * 3 * 2048 * 1408 + 2048 * 64 + 6 * 8 / 64 * 3 * 2048 * 1408
    L = c["num_hidden_layers"]
    params = L * mla + 3 * 2048 * 11264 + (L - 1) * moe + 2048 * 20480
    attention = L * 3 * 16 * (2 * 192 + 2 * 128) * 1025 / 2
    assert per_token == pytest.approx(params + attention / 6)
    ctx = {"counters": {"steps": 3, "train_tokens_per_s": 1000.0, "config": c},
           "cell": tiny_cell(), "peaks": PEAKS}
    assert m.read(ctx) == pytest.approx(100 * m.flops_per_token(c) * 1000 / 197e12)
