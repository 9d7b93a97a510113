"""``required_work_hybrid_ssm_lm.py`` and the hybrid family's weight shapes
against numbers reckoned by hand from the published widths, and the two
selective-scan readers on a made-up reduced trace."""
import pytest

import bench_paths as bp
from harness import cells, peaks
from harness import required_work_hybrid_ssm_lm as w
from harness import weights_hybrid_ssm_lm as hw

JAMBA = dict(vocab_size=65536, d_model=2560, n_heads=20, n_kv_heads=1,
             n_layers=14, d_ff=8192, ssm_expand=2, ssm_state=16,
             ssm_dt_rank=160, ssm_conv=4, dtype="bfloat16",
             layer_types=["mamba"] * 7 + ["attention"] + ["mamba"] * 6)
V5E = peaks.peaks_for("TPU v5 lite")


def test_parameter_count_of_one_period_by_hand():
    mlp = 3 * 2560 * 8192                                    # 62,914,560
    mixer = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 + 16 + 16
             + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    assert mixer == 41241792
    mamba_layer = mixer + mlp + 2 * 2560
    assert mamba_layer == 104161472
    attn_layer = 2560 * (2560 + 2 * 128) + 2560 * 2560 + mlp + 2 * 2560
    assert attn_layer == 76682240
    total = 13 * mamba_layer + attn_layer + 65536 * 2560 + 2560
    assert total == 1598556096
    assert hw.param_count(JAMBA) == total


def test_the_configuration_file_states_these_sizes():
    cell = bp.cell("jamba2_3b_train")
    assert {k: cell.config["model"][k] for k in JAMBA} == JAMBA
    assert cell.config["assumed"]["parameters"] == hw.param_count(JAMBA)
    # attention where i % attn_layer_period == attn_layer_offset
    assert [i for i, k in enumerate(JAMBA["layer_types"])
            if k == "attention"] == [
        i for i in range(cell.config["num_hidden_layers"])
        if i % cell.config["attn_layer_period"]
        == cell.config["attn_layer_offset"]]


def test_one_mamba_layer_and_the_whole_step_by_hand():
    tokens = 4096
    products = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert products == 41123840
    scan = 7 * tokens * 5120 * 16                            # 2,348,810,240
    assert w.scan_forward_flops(JAMBA, 1, 4096) == scan
    in_products = 13 * (products + 3 * 2560 * 8192) + (
        2560 * 2816 + 2560 * 2560 + 3 * 2560 * 8192) + 2560 * 65536
    assert in_products == 1596948480
    assert w.matmul_params(JAMBA) == in_products
    attn = 2 * 2 * (4096 * 4097 // 2) * 2560
    step = 3 * (2 * tokens * in_products + attn + 13 * scan)
    assert w.train_flops_per_step(JAMBA, 1, 4096) == step
    per_token = step / tokens
    assert 9.66e9 < per_token < 9.68e9
    # the whole peak would be 20,379 tokens a second
    assert 197e12 / per_token == pytest.approx(20379, abs=1)


def test_the_scan_is_bound_by_bandwidth_on_the_published_peaks():
    need = w.scan_required_per_step(JAMBA, 1, 4096, V5E)
    wide = 4096 * 5120
    narrow = 2 * 4096 * 16 * 2 + 5120 * 17 * 4
    assert need["ssm_scan_fwd"]["bytes"] == 13 * (wide * (3 * 2 + 4) + narrow)
    assert need["ssm_scan_bwd"]["bytes"] == 13 * (2 * wide * (3 * 2 + 4)
                                                  + 2 * narrow)
    assert need["ssm_scan_fwd"]["bound"] == "hbm"
    assert need["ssm_scan_bwd"]["flops"] == 2 * need["ssm_scan_fwd"]["flops"]
    # about a quarter of a millisecond a layer forward
    assert need["ssm_scan_fwd"]["min_s"] / 13 == pytest.approx(2.57e-4,
                                                               rel=0.02)
    every = w.pallas_required_per_step(JAMBA, 1, 4096, V5E)
    assert set(every) == {"flash_fwd", "flash_bwd", "rmsnorm_fwd",
                          "rmsnorm_bwd", "xent_fwd", "xent_bwd",
                          "ssm_scan_fwd", "ssm_scan_bwd"}
    # 29 norms a pass, one attention layer
    assert every["rmsnorm_fwd"]["bytes"] == 29 * 2 * 4096 * 2560 * 2
    assert every["flash_fwd"]["flops"] == 2 * 2 * (4096 * 4097 // 2) * 2560


class _Device:
    device_kind = "TPU v5 lite"


def _ctx(custom_calls, busy_s=10.0, steps=19):
    cell = bp.cell("jamba2_3b_train")
    return {"cell": cell, "devices": [_Device()], "window": {"steps": steps},
            "trace": {"busy_s": busy_s, "custom_calls": custom_calls,
                      "custom_call_s": sum(v for _k, v in custom_calls)}}


def test_the_scan_readers_pick_the_scans_calls_by_their_operand():
    scan_fwd = ("custom-call:tpu_custom_call bf16[1,4096,5120],"
                "f32[1,32,16,5120]<-bf16[1,4096,5120],f32[1,4096,5120],"
                "bf16[1,4096,5120],bf16[1,4096,16]x2,f32[16,5120],"
                "f32[1,5120]")
    scan_bwd = ("custom-call:tpu_custom_call bf16[1,4096,5120],"
                "f32[1,16,5120]<-bf16[1,4096,5120]x2,f32[16,5120]x1,"
                "f32[1,32,16,5120]")
    other = ("custom-call:tpu_custom_call f32[4096,65536]<-f32[4096,65536],"
             "s32[4096,1],f32[4096,1]x2")
    # a result of that shape is not the operand
    decoy = "custom-call:tpu_custom_call f32[16,5120]<-bf16[4096,2560]"
    ctx = _ctx([(scan_bwd, 1.8), (scan_fwd, 1.5), (other, 0.06),
                (decoy, 0.5)])
    share = cells.load_reader("ssm_scan_time_share.train")(ctx)
    assert share == pytest.approx(100.0 * 3.3 / 10.0)
    need = w.scan_required_per_step(JAMBA, 1, 4096, V5E)
    least = 19 * sum(v["min_s"] for v in need.values())
    roof = cells.load_reader("ssm_scan_roofline")(ctx)
    assert roof == pytest.approx(100.0 * least / 3.3)
    assert 0.0 < roof < 100.0
    # the scan's share is part of all custom calls' share
    assert share <= cells.load_reader("pallas_time_share.train")(ctx)
    # no scan call in the trace, or a family that has none: nothing to read
    assert cells.load_reader("ssm_scan_roofline")(_ctx([(other, 1.0)])) is None
    ctx = _ctx([(scan_fwd, 1.0)])
    ctx["cell"] = bp.cell("pythia14_train")
    assert cells.load_reader("ssm_scan_time_share.train")(ctx) is None
    assert cells.load_reader("ssm_scan_roofline")(ctx) is None
