"""``required_work_mla_moe_lm.py`` and the family's weight shapes against
numbers reckoned by hand from the published widths, and the two
grouped-product readers on a made-up reduced trace."""
import pytest

import bench_paths as bp
from harness import cells, peaks
from harness import required_work_mla_moe_lm as w
from harness import weights_mla_moe_lm as mw

CELL = "dsv2_lite_train"
V5E = peaks.peaks_for("TPU v5 lite")


def model():
    return bp.cell(CELL).config["model"]


def test_parameter_count_of_the_cut_by_hand():
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert mla == 13762560 == w.mla_params(model())
    norms = 2 * 2048 + 512
    dense_layer = mla + norms + 3 * 2048 * 10944
    assert dense_layer == 81007104
    expert_layer = (mla + norms + 2048 * 64 + 3 * 2048 * 2816
                    + 32 * 3 * 2048 * 1408)
    assert expert_layer == 308023808
    total = dense_layer + 4 * expert_layer + 2 * 102400 * 2048 + 2048
    assert total == 1732534784 == mw.param_count(model())
    # the configuration file states what was counted
    config = bp.cell(CELL).config
    assert config["assumed"]["parameters"] == total
    assert config["num_hidden_layers"] == 5 and config["n_routed_experts"] == 32


def test_the_model_section_is_the_sources_keys():
    config, m = bp.cell(CELL).config, model()
    rope = config["rope_scaling"]
    assert (m["d_model"], m["d_ff"], m["moe_d_ff"], m["vocab_size"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"], config["vocab_size"])
    assert (m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["n_heads"]) == (
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
        config["num_attention_heads"])
    assert m["moe_shared_d_ff"] == (config["n_shared_experts"]
                                    * config["moe_intermediate_size"])
    assert (m["n_experts"], m["moe_top_k"]) == (
        config["published"]["n_routed_experts"],
        config["num_experts_per_tok"]) == (64, 6)
    assert m["experts_held"] == list(range(config["n_routed_experts"]))
    assert m["mlp_types"] == ["dense"] * config["first_k_dense_replace"] + [
        "moe"] * (config["num_hidden_layers"]
                  - config["first_k_dense_replace"])
    assert (m["rope_theta"], m["rope_factor"], m["rope_orig_len"],
            m["rope_beta_fast"], m["rope_beta_slow"], m["rope_mscale"],
            m["rope_mscale_all_dim"]) == (
        config["rope_theta"], rope["factor"],
        rope["original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"], rope["mscale"], rope["mscale_all_dim"])
    assert m["moe_renormalize"] is config["norm_topk_prob"] is False
    assert config["routed_scaling_factor"] == 1       # no key: a factor of 1
    assert not m["tie_embeddings"] and not config["tie_word_embeddings"]


def test_the_routed_experts_are_counted_at_their_expectation():
    m = model()
    assert w.expected_experts_per_token(m) == 3.0          # 6 x 32 / 64
    per_token = (5 * 13762560 + 3 * 2048 * 10944
                 + 4 * (2048 * 64 + 3 * 2048 * 2816 + 3 * 3 * 2048 * 1408)
                 + 2048 * 102400)
    assert per_token == 519307264 == w.matmul_params_per_token(m)
    # a share that holds every expert counts all six
    assert w.expected_experts_per_token(dict(m, experts_held=[])) == 6.0


def test_attention_is_counted_at_192_and_128_and_the_step_by_hand():
    m = model()
    pairs = 4096 * 4097 // 2
    attn = 2 * pairs * 16 * (192 + 128)
    assert attn == 85920317440 == w.attention_forward_flops(m, 1, 4096)
    step = 3 * (2 * 4096 * 519307264 + 5 * attn)
    assert step == 14051300081664 == w.train_flops_per_step(m, 1, 4096)
    cell = bp.cell(CELL)
    per_token = cell.family.train_flops_per_item(cell.config, cell.traffic)
    assert per_token == pytest.approx(3.4305e9, rel=1e-4)
    # the whole peak would be 57,426 tokens a second
    assert 197e12 / per_token == pytest.approx(57426, abs=2)


def test_grouped_products_and_every_kernel_of_the_step():
    m = model()
    need = w.gmm_required_per_step(m, 1, 4096, V5E)
    rows = 4096 * 3
    flops = 4 * 3 * 2 * rows * 2048 * 1408
    nbytes = 4 * 3 * (rows * (2048 + 1408) + 32 * 2048 * 1408) * 2
    for kernel in ("gmm_fwd", "gmm_dx", "gmm_dw"):
        assert need[kernel]["flops"] == flops
        assert need[kernel]["bytes"] == nbytes
    # 384 rows an expert sit at the ridge: 4.32 ms of operations a pass,
    # 3.95 ms of bytes (the matrices' two thirds of them)
    assert need["gmm_fwd"]["bound"] == "flops"
    assert need["gmm_fwd"]["min_s"] == pytest.approx(flops / 197e12)
    assert nbytes / 819e9 == pytest.approx(3.95e-3, rel=0.01)
    every = w.pallas_required_per_step(m, 1, 4096, V5E)
    assert set(every) == {"flash_fwd", "flash_bwd", "rmsnorm_fwd",
                          "rmsnorm_bwd", "xent_fwd", "xent_bwd", "gmm_fwd",
                          "gmm_dx", "gmm_dw"}
    assert every["flash_fwd"]["flops"] == 5 * 85920317440
    assert every["flash_fwd"]["bytes"] == 5 * (
        2 * 4096 * 16 * 192 * 2 + 2 * 4096 * 16 * 128 * 2 + 16 * 4096 * 4)
    # 11 norms of 2048 and 5 of the latent's 512 a pass
    assert every["rmsnorm_fwd"]["bytes"] == 2 * 2 * 4096 * (11 * 2048
                                                            + 5 * 512)
    assert every["xent_fwd"]["bytes"] == 4096 * 102400 * 4


class _Device:
    device_kind = "TPU v5 lite"


def _ctx(custom_calls, busy_s=10.0, steps=50, cell=CELL):
    return {"cell": bp.cell(cell), "devices": [_Device()],
            "window": {"steps": steps},
            "trace": {"busy_s": busy_s, "custom_calls": custom_calls,
                      "custom_call_s": sum(v for _k, v in custom_calls)}}


def test_the_gmm_readers_pick_the_grouped_products_by_the_stacked_matrix():
    fwd = ("custom-call:tpu_custom_call bf16[24576,1408]<-s32[34],s32[80]x2,"
           "s32[1],bf16[24576,2048],bf16[32,2048,1408]")
    dx = ("custom-call:tpu_custom_call bf16[24576,1408]<-s32[34],s32[80]x2,"
          "s32[1],bf16[24576,2048],bf16[32,1408,2048]")
    dw = ("custom-call:tpu_custom_call bf16[32,2048,1408]<-s32[33],"
          "s32[79]x2,s32[1],bf16[24576,2048],bf16[24576,1408]")
    flash = ("custom-call:tpu_custom_call bf16[1,16,4096,128],"
             "f32[1,16,4096,1]<-bf16[1,16,4096,192]x2,bf16[1,16,4096,128]")
    ctx = _ctx([(dw, 1.2), (fwd, 0.9), (dx, 0.8), (flash, 0.5)])
    share = cells.load_reader("moe_gmm_time_share.train")(ctx)
    assert share == pytest.approx(100.0 * 2.9 / 10.0)
    need = w.gmm_required_per_step(model(), 1, 4096, V5E)
    least = 50 * sum(v["min_s"] for v in need.values())
    roof = cells.load_reader("moe_gmm_roofline")(ctx)
    assert roof == pytest.approx(100.0 * least / 2.9)
    assert 0.0 < roof < 100.0
    assert share <= cells.load_reader("pallas_time_share.train")(ctx)
    # nothing to read: no such call, or a family without the product
    for reader in ("moe_gmm_time_share.train", "moe_gmm_roofline"):
        assert cells.load_reader(reader)(_ctx([(flash, 1.0)])) is None
        assert cells.load_reader(reader)(
            _ctx([(fwd, 1.0)], cell="pythia14_train")) is None
        ctx = _ctx([(fwd, 1.0)])
        ctx["trace"] = None
        assert cells.load_reader(reader)(ctx) is None
