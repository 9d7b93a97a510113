"""``required_work_swa_moe_lm.py`` and the family's weight shapes against
numbers reckoned by hand from the published widths: the band's pair count,
the 2.118 GFLOP a token, the kernels' operations and bytes; and the two
flash readers on a made-up reduced trace."""
import pytest

import bench_paths as bp
from harness import cells, peaks
from harness import required_work_swa_moe_lm as w
from harness import weights_swa_moe_lm as sw

CELL = "smallthinker_train_s16k"
V5E = peaks.peaks_for("TPU v5 lite")
CAUSAL, BAND = 134225920, 58722304


def model():
    return bp.cell(CELL).config["model"]


def test_parameter_count_of_the_cut_by_hand():
    attn = 2560 * (28 + 4 + 4) * 128 + 28 * 128 * 2560
    assert attn == 20971520 == w.attention_params(model())
    layer = attn + 2 * 2560 + 2560 * 64 + 16 * 3 * 2560 * 768
    assert layer == 115512320
    total = 4 * layer + 2 * 37984 * 2560 + 2560
    assert total == 656529920 == sw.param_count(model())
    config = bp.cell(CELL).config
    assert config["assumed"]["parameters"] == total
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37984)


def test_the_model_section_is_the_sources_keys():
    """Every published width unchanged; the cut and the deployment written
    beside them."""
    config, m = bp.cell(CELL).config, model()
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"]) == (
        2560, 28, 4, 128)
    assert (m["moe_d_ff"], m["n_experts"], m["moe_top_k"]) == (
        config["moe_ffn_hidden_size"],
        config["published"]["moe_num_primary_experts"],
        config["moe_num_active_primary_experts"]) == (768, 64, 6)
    assert m["experts_held"] == list(range(
        config["moe_num_primary_experts"]))
    layers = config["num_hidden_layers"]
    assert m["n_layers"] == layers == 4
    # one whole period of the published layouts, which the file copies whole
    assert len(config["sliding_window_layout"]) == len(
        config["rope_layout"]) == config["published"][
            "num_hidden_layers"] == 52
    assert config["sliding_window_layout"] == config["rope_layout"] == [
        0, 1, 1, 1] * 13
    assert m["attn_windows"] == [config["sliding_window_size"] * on for on
                                 in config["sliding_window_layout"][:layers]
                                 ] == [0, 4096, 4096, 4096]
    assert m["attn_rope"] == config["rope_layout"][:layers]
    assert m["rope_theta"] == config["rope_theta"] == 1500000
    assert config["rope_scaling"] is None and "rope_factor" not in m
    assert m["moe_renormalize"] is config["norm_topk_prob"] is True
    assert config["moe_primary_router_apply_softmax"] is True
    assert m["mlp"] == "reglu" and m["moe_router_pre_attention"] is True
    assert m["vocab_size"] == config["vocab_size"] == config["published"][
        "vocab_size"] // 4
    assert m["max_len"] == config["max_position_embeddings"] == 16384
    assert not m["tie_embeddings"] and not config["tie_word_embeddings"]
    for key in ("cut", "deployment"):
        assert "4 chips" in config[key]
    assert set(config["assumed"]) >= {"router_input", "window_edge",
                                      "rope_layout", "balance_term"}


@pytest.mark.parametrize("seq,window,pairs", [
    (16384, 0, CAUSAL), (16384, 4096, BAND), (16384, 16384, CAUSAL),
    (16384, 20000, CAUSAL), (8, 3, 1 + 2 + 3 * 6), (8, 1, 8)])
def test_the_bands_pair_count(seq, window, pairs):
    assert w.pairs_a_head(seq, window) == pairs
    # against the mask itself
    if seq <= 64:
        assert pairs == sum(1 for t in range(seq) for j in range(seq)
                            if 0 <= t - j and (not window or t - j < window))


def test_the_band_holds_43_7_percent_of_the_causal_pairs():
    assert BAND / CAUSAL == pytest.approx(0.4375, abs=2e-4)


def test_attention_counts_the_band_on_window_layers_and_the_step_by_hand():
    m = model()
    full, window = 4 * CAUSAL * 28 * 128, 4 * BAND * 28 * 128
    assert full == 1924262789120 == w.attention_forward_flops(m, 1, 16384, 0)
    assert window == 841842950144 == w.attention_forward_flops(
        m, 1, 16384, 4096)
    assert w.expected_experts_per_token(m) == 1.5          # 6 x 16 / 64
    per_token = 4 * (20971520 + 2560 * 64 + 1.5 * 3 * 2560 * 768
                     ) + 2560 * 37984
    assert per_token == 217169920 == w.matmul_params_per_token(m)
    step = 3 * (2 * 16384 * 217169920 + full + 3 * window)
    assert step == 34698046734336 == w.train_flops_per_step(m, 1, 16384)
    cell = bp.cell(CELL)
    per_item = cell.family.train_flops_per_item(cell.config, cell.traffic)
    assert per_item == 2117800704                     # 2.118 GFLOP a token
    # the whole peak would be 93,021 tokens a second
    assert 197e12 / per_item == pytest.approx(93021, abs=1)
    # counted as causal everywhere it would be a third more
    causal_everywhere = 3 * (2 * 16384 * 217169920 + 4 * full)
    assert causal_everywhere / step == pytest.approx(1.281, abs=1e-3)


def test_every_kernel_of_the_step():
    m = model()
    every = w.pallas_required_per_step(m, 1, 16384, V5E)
    assert set(every) == {"flash_fwd", "flash_dq", "flash_dkv",
                          "rmsnorm_fwd", "rmsnorm_bwd", "xent_fwd",
                          "xent_bwd", "gmm_fwd", "gmm_dx", "gmm_dw"}
    flash = w.flash_required_per_step(m, 1, 16384, V5E)
    attn = 1924262789120 + 3 * 841842950144
    wide, narrow, row = 16384 * 28 * 128 * 2, 16384 * 4 * 128 * 2, \
        28 * 16384 * 4
    for kernel, nbytes in (("flash_fwd", 2 * wide + 2 * narrow + row),
                           ("flash_dq", 3 * wide + 2 * narrow + 2 * row),
                           ("flash_dkv", 2 * wide + 4 * narrow + 2 * row)):
        assert flash[kernel]["flops"] == attn == every[kernel]["flops"]
        assert flash[kernel]["bytes"] == 4 * nbytes
        assert flash[kernel]["bound"] == "flops"
        assert flash[kernel]["min_s"] == pytest.approx(attn / 197e12)
    # 22.6 ms a pass at the peak: 67.8 ms a step for the three kernels
    assert sum(v["min_s"] for v in flash.values()) == pytest.approx(
        0.06776, rel=1e-3)
    rows = 16384 * 1.5
    flops = 4 * 3 * 2 * rows * 2560 * 768
    nbytes = 4 * 3 * (rows * (2560 + 768) + 16 * 2560 * 768) * 2
    for kernel in ("gmm_fwd", "gmm_dx", "gmm_dw"):
        assert every[kernel]["flops"] == flops
        assert every[kernel]["bytes"] == nbytes
        assert every[kernel]["bound"] == "flops"
    assert every["rmsnorm_fwd"]["bytes"] == 2 * 2 * 9 * 16384 * 2560
    assert every["xent_fwd"]["bytes"] == 16384 * 37984 * 4


class _Device:
    device_kind = "TPU v5 lite"


def _ctx(custom_calls, busy_s=20.0, steps=20, cell=CELL):
    return {"cell": bp.cell(cell), "devices": [_Device()],
            "window": {"steps": steps},
            "trace": {"busy_s": busy_s, "custom_calls": custom_calls,
                      "custom_call_s": sum(v for _k, v in custom_calls)}}


def test_the_flash_readers_pick_the_kernels_by_q_heads_first():
    q, row = "bf16[1,28,16384,128]", "f32[1,28,16384,1]"
    fwd = "custom-call:tpu_custom_call %s,%s<-%sx3" % (q, row, q)
    dq = "custom-call:tpu_custom_call %s<-%sx4,%sx2" % (q, q, row)
    dkv = "custom-call:tpu_custom_call %sx2<-%sx4,%sx2" % (q, q, row)
    gmm = ("custom-call:tpu_custom_call bf16[98304,768]<-s32[18],s32[784]x2,"
           "s32[1],bf16[98304,2560],bf16[16,2560,768]")
    norm = ("custom-call:tpu_custom_call bf16[16384,2560]<-bf16[16384,2560],"
            "bf16[1,2560]")
    ctx = _ctx([(dkv, 2.4), (dq, 2.0), (fwd, 1.6), (gmm, 1.5), (norm, 0.2)])
    share = cells.load_reader("flash_time_share.train")(ctx)
    assert share == pytest.approx(100.0 * 6.0 / 20.0)
    need = w.flash_required_per_step(model(), 1, 16384, V5E)
    least = 20 * sum(v["min_s"] for v in need.values())
    roof = cells.load_reader("flash_roofline")(ctx)
    assert roof == pytest.approx(100.0 * least / 6.0)
    assert 0.0 < roof < 100.0
    assert share <= cells.load_reader("pallas_time_share.train")(ctx)
    # the grouped product's two readers find theirs through the same family
    assert cells.load_reader("moe_gmm_time_share.train")(ctx) == \
        pytest.approx(100.0 * 1.5 / 20.0)
    assert 0.0 < cells.load_reader("moe_gmm_roofline")(ctx) < 100.0
    # nothing to read: no such call, or a family without the hook
    for reader in ("flash_time_share.train", "flash_roofline"):
        assert cells.load_reader(reader)(_ctx([(gmm, 1.0)])) is None
        assert cells.load_reader(reader)(
            _ctx([(fwd, 1.0)], cell="pythia14_train")) is None
        ctx = _ctx([(fwd, 1.0)])
        ctx["trace"] = None
        assert cells.load_reader(reader)(ctx) is None
