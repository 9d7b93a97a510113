"""``required_work_gated_swa_moe_lm.py`` and the family's weight shapes
against numbers reckoned by hand from the published widths: the parameter
count, the 3.73 GFLOP a token, the pairs each mask keeps, the kernels'
operations and bytes at each head count; the configuration file against the
source's keys; and the two new readers, with the flash and grouped-product
ones, on a made-up reduced trace."""
import math

import pytest

import bench_paths as bp
from harness import cells, peaks
from harness import required_work_gated_swa_moe_lm as w
from harness import required_work_swa_moe_lm as swa_work
from harness import weights_gated_swa_moe_lm as gw

CELL = "laguna_s21_train_s8k"
V5E = peaks.peaks_for("TPU v5 lite")
E, D, KV = 3072, 128, 8


def model():
    return bp.cell(CELL).config["model"]


def attn_params(heads):
    return E * (heads + 2 * KV) * D + heads * D * E + E * heads


def test_parameter_count_of_the_cut_by_hand():
    full, window = attn_params(48), attn_params(72)
    assert (full, window) == (44187648, 63135744)
    expert = 3 * E * 1024
    dense = 3 * E * 12288
    moe = E * 256 + expert + 16 * expert           # router, shared, held
    norms = 5 * 2 * E + E
    total = (full + dense + 3 * (window + moe) + (full + moe)
             + 2 * 12544 * E + norms)
    assert total == 1113007104 == gw.param_count(model())
    config = bp.cell(CELL).config
    assert config["assumed"]["parameters"] == total
    # 6 bytes a trained parameter: 6.68 GB with the gradient
    assert 6 * total == pytest.approx(6.678e9, rel=1e-3)


def test_the_model_section_is_the_sources_keys():
    """Every published width unchanged; the kept layers are published
    layers 0-4; the cut and the deployment written beside them."""
    config, m = bp.cell(CELL).config, model()
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    pub = config["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 256, 100352)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 12544)
    assert m["vocab_size"] == pub["vocab_size"] // 8
    assert m["d_model"] == config["hidden_size"] == E
    assert (m["n_heads"], m["n_kv_heads"], m["head_dim"]) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"]) == (48, KV, D)
    assert m["attn_heads"] == config["num_attention_heads_per_layer"][:5]
    kinds = config["layer_types"][:5]
    assert m["attn_windows"] == [config["sliding_window"] * (
        t == "sliding_attention") for t in kinds] == [0, 512, 512, 512, 0]
    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    for setting, kind in zip(m["attn_rope"], kinds):
        want = full if kind == "full_attention" else window
        assert setting["theta"] == want["rope_theta"]
        assert setting.get("fraction", 1) == want["partial_rotary_factor"]
        if want["rope_type"] == "yarn":
            assert (setting["factor"], setting["orig_len"],
                    setting["beta_fast"], setting["beta_slow"]) == (
                want["factor"], want["original_max_position_embeddings"],
                want["beta_fast"], want["beta_slow"])
            # the published attention factor is YaRN's own temperature
            assert want["attention_factor"] == pytest.approx(
                0.1 * math.log(want["factor"]) + 1, rel=1e-15)
        else:
            assert set(setting) == {"theta"}
    assert m["attn_gate"] == "per_head" and config["gating"] == "per-head"
    assert set(config["gating_types"]) == {"per_head"}
    assert m["mlp_types"] == [{"dense": "dense", "sparse": "moe"}[t]
                              for t in config["mlp_layer_types"][:5]]
    assert config["mlp_only_layers"] == [0]
    assert m["d_ff"] == config["intermediate_size"] == 12288
    assert m["moe_d_ff"] == config["moe_intermediate_size"] == 1024
    assert m["moe_shared_d_ff"] == \
        config["shared_expert_intermediate_size"] == 1024
    assert m["n_experts"] == pub["num_experts"] == 256
    assert m["experts_held"] == list(range(config["num_experts"]))
    assert m["moe_top_k"] == config["num_experts_per_tok"] == 10
    assert m["moe_routed_scale"] == config["moe_routed_scaling_factor"] == 2.5
    assert m["moe_renormalize"] is config["norm_topk_prob"] is True
    assert config["moe_apply_router_weight_on_input"] is False
    assert config["moe_router_logit_softcapping"] == 0
    assert m["norm_eps"] == config["rms_norm_eps"] == 1e-6
    assert m["tie_embeddings"] is config["tie_word_embeddings"] is False
    assert config["attention_bias"] is False
    assert m["n_layers"] == config["num_hidden_layers"] == 5
    for key in ("cut", "deployment"):
        assert "16 chips" in config[key]
    assert set(config["assumed"]) >= {
        "block", "window_edge", "gate", "rotary", "dense_mlp", "router",
        "shared_expert", "balance_term", "init", "optimizer", "parameters"}


def test_the_pairs_each_mask_keeps():
    assert swa_work.pairs_a_head(8192, 0) == 8192 * 8193 // 2 == 33558528
    band = 512 * 513 // 2 + (8192 - 512) * 512
    assert swa_work.pairs_a_head(8192, 512) == band == 4063488
    assert w.window_layers(model()) == [1, 2, 3]


def test_the_step_by_hand():
    m = model()
    assert w.expected_experts_per_token(m) == 0.625       # 10 x 16 / 256
    expert = 3 * E * 1024
    per_token = (2 * attn_params(48) + 3 * attn_params(72)
                 + 3 * E * 12288
                 + 4 * (E * 256 + expert + 0.625 * expert)
                 + E * 12544)
    assert per_token == 494051328 == w.matmul_params_per_token(m)
    full = 4 * 33558528 * 48 * D
    band = 4 * 4063488 * 72 * D
    assert 2 * full + 3 * band == 2098858033152 == \
        w.attention_forward_flops(m, 1, 8192)
    step = 3 * (2 * 8192 * per_token + 2 * full + 3 * band)
    assert step == w.train_flops_per_step(m, 1, 8192)
    cell = bp.cell(CELL)
    per_item = cell.family.train_flops_per_item(cell.config, cell.traffic)
    assert per_item == step / 8192 == 3732932736          # 3.73 GFLOP
    # attention of both kinds is about two thirds of the forward's work
    attn = 2 * 8192 * (2 * attn_params(48) + 3 * attn_params(72)) \
        + 2 * full + 3 * band
    assert attn / (step / 3) == pytest.approx(0.65, abs=0.01)


def test_every_kernel_of_the_step_at_each_head_count():
    m = model()
    every = w.pallas_required_per_step(m, 1, 8192, V5E)
    assert set(every) == {"flash_fwd", "flash_dq", "flash_dkv",
                          "rmsnorm_fwd", "rmsnorm_bwd", "xent_fwd",
                          "xent_bwd", "gmm_fwd", "gmm_dx", "gmm_dw"}
    heads = 2 * 48 + 3 * 72
    wide, narrow, row = heads * 8192 * D * 2, 5 * 8192 * KV * D * 2, \
        heads * 8192 * 4
    flops = 2098858033152
    for kernel, nbytes in (("flash_fwd", 2 * wide + 2 * narrow + row),
                           ("flash_dq", 3 * wide + 2 * narrow + 2 * row),
                           ("flash_dkv", 2 * wide + 4 * narrow + 2 * row)):
        assert every[kernel]["flops"] == flops
        assert every[kernel]["bytes"] == nbytes
        assert every[kernel]["bound"] == "flops"
    band = w.flash_required_per_step(m, 1, 8192, V5E, w.window_layers(m))
    wide, narrow, row = 216 * 8192 * D * 2, 3 * 8192 * KV * D * 2, \
        216 * 8192 * 4
    assert band["flash_fwd"]["flops"] == 3 * 4 * 4063488 * 72 * D
    assert band["flash_fwd"]["bytes"] == 2 * wide + 2 * narrow + row
    # the band's few pairs leave each call near the bound of its bytes
    assert band["flash_fwd"]["bound"] == "flops"
    rows = 8192 * 0.625
    assert every["gmm_fwd"]["flops"] == 4 * 3 * 2 * rows * E * 1024
    assert every["gmm_fwd"]["bytes"] == (4 * 3 * rows * (E + 1024) * 2
                                         + 4 * 3 * 16 * E * 1024 * 2)
    assert every["xent_fwd"]["bytes"] == 8192 * 12544 * 4
    assert every["rmsnorm_fwd"]["bytes"] == 2 * 2 * 11 * 8192 * E


class _Device:
    device_kind = "TPU v5 lite"


def _ctx(custom_calls, busy_s=10.0, steps=20, cell=CELL):
    return {"cell": bp.cell(cell), "devices": [_Device()],
            "window": {"steps": steps},
            "trace": {"busy_s": busy_s, "custom_calls": custom_calls,
                      "custom_call_s": sum(v for _k, v in custom_calls)}}


def _flash(heads):
    q, row = "bf16[1,%d,8192,128]" % heads, "f32[1,%d,8192,1]" % heads
    return ("custom-call:tpu_custom_call %s,%s<-%sx3" % (q, row, q),
            "custom-call:tpu_custom_call %sx2<-%sx4,%sx2" % (q, q, row))


GMM = ("custom-call:tpu_custom_call bf16[81920,1024]<-s32[18],s32[656]x2,"
       "s32[1],bf16[81920,3072],bf16[16,3072,1024]")
GATHER = ("custom-call:tpu_custom_call bf16[8192,3072]<-s32[81920],s32[64],"
          "f32[8192,20],u32[81920,1,1536]")


def test_the_band_readers_pick_the_window_layers_calls_by_their_heads():
    full_fwd, full_bwd = _flash(48)
    band_fwd, band_bwd = _flash(72)
    ctx = _ctx([(full_fwd, 0.6), (full_bwd, 1.5), (band_fwd, 0.5),
                (band_bwd, 1.4), (GMM, 0.8), (GATHER, 0.3)])
    share = cells.load_reader("flash_band_time_share.train")(ctx)
    assert share == pytest.approx(100.0 * 1.9 / 10.0)
    need = w.flash_required_per_step(model(), 1, 8192, V5E, [1, 2, 3])
    least = 20 * sum(v["min_s"] for v in need.values())
    roof = cells.load_reader("flash_band_roofline")(ctx)
    assert roof == pytest.approx(100.0 * least / 1.9)
    assert 0.0 < roof < 100.0
    # the flash readers find both head counts, the grouped product's its own
    assert cells.load_reader("flash_time_share.train")(ctx) == \
        pytest.approx(100.0 * 4.0 / 10.0)
    assert 0.0 < cells.load_reader("flash_roofline")(ctx) < 100.0
    assert cells.load_reader("moe_gmm_time_share.train")(ctx) == \
        pytest.approx(100.0 * 0.8 / 10.0)
    # nothing to read: no band call, a family without the hook, no trace
    for reader in ("flash_band_time_share.train", "flash_band_roofline"):
        assert cells.load_reader(reader)(
            _ctx([(full_fwd, 1.0), (GMM, 1.0)])) is None
        assert cells.load_reader(reader)(
            _ctx([(band_fwd, 1.0)], cell="smallthinker_train_s16k")) is None
        ctx = _ctx([(band_fwd, 1.0)])
        ctx["trace"] = None
        assert cells.load_reader(reader)(ctx) is None


def test_a_band_whose_head_count_a_full_layer_shares_is_refused():
    cell = bp.cell(CELL)
    config = dict(cell.config, model=dict(cell.config["model"],
                                          attn_heads=[72, 72, 72, 72, 48]))
    with pytest.raises(AssertionError, match="cannot be told apart"):
        cell.family.flash_band_call_seconds(config, cell.traffic, [])
