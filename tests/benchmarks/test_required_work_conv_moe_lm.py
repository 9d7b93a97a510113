"""``required_work_conv_moe_lm.py`` and the family's weight shapes against
numbers reckoned by hand from the published widths: the parameter count,
the 1.298 GFLOP a token, the kernels' operations and bytes at the head's
true width; the configuration file against the source's keys; and the two
new readers, with the flash and grouped-product ones, on a made-up reduced
trace."""
import pytest

import bench_paths as bp
from harness import cells, peaks
from harness import required_work_conv_moe_lm as w
from harness import weights_conv_moe_lm as cw

CELL = "lfm2_8b_train_s8k"
V5E = peaks.peaks_for("TPU v5 lite")


def model():
    return bp.cell(CELL).config["model"]


def test_parameter_count_of_the_cut_by_hand():
    conv = 2048 * 3 * 2048 + 3 * 2048 + 2048 * 2048
    attn = 2048 * (32 + 8 + 8) * 64 + 32 * 64 * 2048 + 2 * 64
    dense = 3 * 2048 * 7168
    moe = 2048 * 32 + 32 + 8 * 3 * 2048 * 1792
    norms = 2 * 2048
    total = (4 * conv + attn + dense + 4 * moe + 5 * norms
             + 16384 * 2048 + 2048)
    assert total == 507820288 == cw.param_count(model())
    config = bp.cell(CELL).config
    assert config["assumed"]["parameters"] == total
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 16384)
    # 6 bytes a trained parameter: 3.05 GB with the gradient
    assert 6 * total == pytest.approx(3.047e9, rel=1e-3)


def test_the_model_section_is_the_sources_keys():
    """Every published width unchanged; the cut and the deployment written
    beside them."""
    config, m = bp.cell(CELL).config, model()
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    pub = config["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (24, 32, 65536)
    assert m["d_model"] == config["hidden_size"] == 2048
    assert (m["n_heads"], m["n_kv_heads"]) == (
        config["num_attention_heads"], config["num_key_value_heads"]) == (
        32, 8)
    assert m["head_dim"] == 2048 // 32 == 64
    assert m["d_ff"] == config["intermediate_size"] == 7168
    assert m["moe_d_ff"] == config["moe_intermediate_size"] == 1792
    assert m["n_experts"] == pub["num_experts"] == 32
    assert m["experts_held"] == list(range(config["num_experts"]))
    assert m["moe_top_k"] == config["num_experts_per_tok"] == 4
    assert m["short_conv"] == config["conv_L_cache"] == 3
    assert config["conv_bias"] is False
    assert m["norm_eps"] == config["norm_eps"] == 1e-5
    assert m["rope_theta"] == config["rope_theta"] == 1000000
    assert m["moe_renormalize"] is config["norm_topk_prob"] is True
    assert config["routed_scaling_factor"] == 1
    assert m["moe_expert_bias"] is config["use_expert_bias"] is True
    assert m["moe_router"] == "sigmoid" and m["qk_norm"]
    assert m["tie_embeddings"] and m["moe_aux_weight"] == 0
    assert m["vocab_size"] == config["vocab_size"] == pub["vocab_size"] // 4
    # published layers 1-5: the second leading dense layer, then one whole
    # period of the expert layers
    layers = config["layer_types"]
    assert len(layers) == pub["num_hidden_layers"] == 24
    assert layers.count("conv") == 18
    kept = ["attention" if t == "full_attention" else t
            for t in layers[1:6]]
    assert m["layer_types"] == kept == ["conv", "attention", "conv", "conv",
                                        "conv"]
    assert m["mlp_types"] == ["dense" if i < config["num_dense_layers"]
                              else "moe" for i in range(1, 6)]
    assert m["attn_rope"] == [int(t == "attention") for t in kept]
    assert m["n_layers"] == config["num_hidden_layers"] == 5
    for key in ("cut", "deployment"):
        assert "4 chips" in config[key]
    assert set(config["assumed"]) >= {"tie", "bias_update", "init", "conv",
                                      "balance_term", "optimizer"}


def test_the_step_by_hand():
    m = model()
    assert w.expected_experts_per_token(m) == 1.0          # 4 x 8 / 32
    per_token = (4 * (2048 * 6144 + 2048 * 2048)
                 + 2048 * 3072 + 2048 * 2048
                 + 3 * 2048 * 7168
                 + 4 * (2048 * 32 + 3 * 2048 * 1792)
                 + 2048 * 16384)
    assert per_token == 199491584 == w.matmul_params_per_token(m)
    attn = 4 * 4 * (8192 * 8193 // 2) * 32 * 64
    assert attn == 1099645845504 == w.attention_forward_flops(m, 4, 8192)
    step = 3 * (2 * 32768 * per_token + attn)
    assert step == 42520578883584 == w.train_flops_per_step(m, 4, 8192)
    cell = bp.cell(CELL)
    per_item = cell.family.train_flops_per_item(cell.config, cell.traffic)
    assert per_item == 1297625088                      # 1.298 GFLOP a token
    # the whole peak would be 151,816 tokens a second
    assert 197e12 / per_item == pytest.approx(151816, abs=1)


def test_every_kernel_of_the_step_at_the_heads_true_width():
    m = model()
    every = w.pallas_required_per_step(m, 4, 8192, V5E)
    assert set(every) == {"flash_fwd", "flash_dq", "flash_dkv",
                          "rmsnorm_fwd", "rmsnorm_bwd", "xent_fwd",
                          "xent_bwd", "gmm_fwd", "gmm_dx", "gmm_dw",
                          "short_conv_fwd", "short_conv_bwd"}
    attn = 1099645845504
    tokens = 4 * 8192
    wide, narrow, row = tokens * 32 * 64 * 2, tokens * 8 * 64 * 2, \
        4 * 32 * 8192 * 4
    for kernel, nbytes in (("flash_fwd", 2 * wide + 2 * narrow + row),
                           ("flash_dq", 3 * wide + 2 * narrow + 2 * row),
                           ("flash_dkv", 2 * wide + 4 * narrow + 2 * row)):
        assert every[kernel]["flops"] == attn
        assert every[kernel]["bytes"] == nbytes       # 64 wide, not 128
        assert every[kernel]["bound"] == "flops"
    plane = tokens * 2048 * 2
    assert every["short_conv_fwd"]["bytes"] == 4 * 4 * plane
    assert every["short_conv_bwd"]["bytes"] == 4 * 7 * plane
    assert every["short_conv_fwd"]["bound"] == "hbm"
    # 2.62 + 4.59 ms a step at 819 GB/s
    assert every["short_conv_fwd"]["min_s"] == pytest.approx(
        16 * plane / 819e9)
    assert every["short_conv_bwd"]["min_s"] == pytest.approx(
        28 * plane / 819e9)
    rows = tokens * 1.0
    assert every["gmm_fwd"]["flops"] == 4 * 3 * 2 * rows * 2048 * 1792
    assert every["xent_fwd"]["bytes"] == tokens * 16384 * 4
    assert every["rmsnorm_fwd"]["bytes"] == 2 * 2 * 11 * tokens * 2048


class _Device:
    device_kind = "TPU v5 lite"


def _ctx(custom_calls, busy_s=20.0, steps=20, cell=CELL):
    return {"cell": bp.cell(cell), "devices": [_Device()],
            "window": {"steps": steps},
            "trace": {"busy_s": busy_s, "custom_calls": custom_calls,
                      "custom_call_s": sum(v for _k, v in custom_calls)}}


PLANE = "bf16[4,8192,2048]"
CONV_FWD = "custom-call:tpu_custom_call %s<-%sx5,bf16[3,2048]" % (
    PLANE, PLANE)
CONV_BWD = ("custom-call:tpu_custom_call %sx3,f32[4,16,3,2048]<-%sx8,"
            "bf16[3,2048]" % (PLANE, PLANE))
Q, ROW = "bf16[4,32,8192,64]", "f32[4,32,8192,1]"
FLASH = "custom-call:tpu_custom_call %s,%s<-%sx3" % (Q, ROW, Q)
GMM = ("custom-call:tpu_custom_call bf16[131072,1792]<-s32[18],s32[784]x2,"
       "s32[1],bf16[131072,2048],bf16[8,2048,1792]")
NORM = ("custom-call:tpu_custom_call bf16[32768,2048]<-bf16[32768,2048],"
        "bf16[1,2048]")
# a kernel that takes a [3, 2048] as part of a wider shape is not the conv
DECOY = ("custom-call:tpu_custom_call bf16[3,20480]<-bf16[3,20480],"
         "bf16[3,2048,4]")


def test_the_short_conv_readers_pick_the_kernels_by_their_taps():
    ctx = _ctx([(CONV_BWD, 0.3), (CONV_FWD, 0.25), (FLASH, 1.0), (GMM, 1.5),
                (NORM, 0.2), (DECOY, 0.7)])
    share = cells.load_reader("short_conv_time_share.train")(ctx)
    assert share == pytest.approx(100.0 * 0.55 / 20.0)
    need = w.short_conv_required_per_step(model(), 4, 8192, V5E)
    least = 20 * sum(v["min_s"] for v in need.values())
    roof = cells.load_reader("short_conv_roofline")(ctx)
    assert roof == pytest.approx(100.0 * least / 0.55)
    assert 0.0 < roof < 100.0
    # the flash and grouped-product readers find theirs through the family
    assert cells.load_reader("flash_time_share.train")(ctx) == \
        pytest.approx(100.0 * 1.0 / 20.0)
    assert 0.0 < cells.load_reader("flash_roofline")(ctx) < 100.0
    assert cells.load_reader("moe_gmm_time_share.train")(ctx) == \
        pytest.approx(100.0 * 1.5 / 20.0)
    # nothing to read: no such call, a family without the hook, no trace
    for reader in ("short_conv_time_share.train", "short_conv_roofline"):
        assert cells.load_reader(reader)(_ctx([(GMM, 1.0), (DECOY, 1.0)])) \
            is None
        assert cells.load_reader(reader)(
            _ctx([(CONV_FWD, 1.0)], cell="smallthinker_train_s16k")) is None
        ctx = _ctx([(CONV_FWD, 1.0)])
        ctx["trace"] = None
        assert cells.load_reader(reader)(ctx) is None
