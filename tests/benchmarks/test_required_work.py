"""``required_work.py`` against numbers reckoned by hand."""
import pytest

import bench_paths  # noqa: F401  (puts the benchmark on the path)
from harness import peaks, required_work as w

PYTHIA14 = dict(vocab_size=50304, d_model=2048, n_heads=16, n_layers=24,
                d_ff=8192)
V5E = peaks.peaks_for("TPU v5 lite")


def test_parameter_counts():
    per_layer = 2048 * 6144 + 2048 * 2048 + 2 * 2048 * 8192   # 50,331,648
    assert per_layer == 50331648
    assert w.lm_matmul_params(PYTHIA14) == 24 * per_layer + 2048 * 50304
    assert w.lm_param_count(PYTHIA14) == 1414105088


def test_one_layer_forward_flops_by_hand():
    tokens = 4 * 2048
    dense = 2 * tokens * 50331648                  # 824,633,720,832
    # causal attention: query t sees t + 1 keys; QK^T and PV, 2 ops a MAC,
    # over all heads together a row of d_model
    pairs = 4 * (2048 * 2049 // 2)
    attn = 2 * 2 * pairs * 2048                    # 68,753,031,168
    assert w.attention_forward_flops(PYTHIA14, 4, 2048) == attn
    assert w.lm_layer_forward_flops(PYTHIA14, 4, 2048) == dense + attn
    # attention is under a tenth of the layer
    assert attn / (dense + attn) < 0.1


def test_head_and_whole_step():
    head = 2 * 8192 * 2048 * 50304
    assert w.lm_head_forward_flops(PYTHIA14, 4, 2048) == head
    step = 3 * (24 * w.lm_layer_forward_flops(PYTHIA14, 4, 2048) + head)
    assert w.lm_train_flops_per_step(PYTHIA14, 4, 2048) == step
    per_token = step / 8192
    # 6 x the parameters in products, plus causal attention
    assert per_token == pytest.approx(6 * w.lm_matmul_params(PYTHIA14)
                                      + 3 * 24 * 2 * 2049 * 2048, rel=1e-12)
    assert 8.4e9 < per_token < 8.5e9


def test_xent_is_bound_by_bandwidth_and_flash_by_operations():
    need = w.pallas_required_per_step(PYTHIA14, 4, 2048, V5E)
    logits = 8192 * 50304 * 4
    assert need["xent_fwd"]["bytes"] == logits
    assert need["xent_bwd"]["bytes"] == 2 * logits
    assert need["xent_fwd"]["bound"] == "hbm"
    assert need["xent_fwd"]["min_s"] == pytest.approx(logits / 819e9)
    assert need["rmsnorm_fwd"]["bound"] == "hbm"
    assert need["rmsnorm_fwd"]["bytes"] == 49 * 2 * 8192 * 2048 * 2
    assert need["flash_fwd"]["bound"] == "flops"
    assert need["flash_fwd"]["flops"] == 24 * w.attention_forward_flops(
        PYTHIA14, 4, 2048)
    assert need["flash_bwd"]["flops"] == 2 * need["flash_fwd"]["flops"]
    # all of it is a small part of a 1.35 s step
    assert sum(v["min_s"] for v in need.values()) < 0.05


def test_decode_bytes_follow_the_live_lengths():
    weights = (w.lm_matmul_params(PYTHIA14) + 24 * 2 * 2048 + 2048) * 2
    one = w.decode_required_bytes(PYTHIA14, [100])
    assert one == weights + 2048 * 2 + 2 * 24 * 100 * 2048 * 2
    full = w.decode_required_bytes(PYTHIA14, [2048] * 32)
    live = w.decode_required_bytes(PYTHIA14, [500] * 32)
    assert full - live == 2 * 24 * 32 * (2048 - 500) * 2048 * 2


def test_resnet50_convolutions_by_hand():
    convs = {n: (i, o, k, h) for n, i, o, k, h in w.resnet50_v1_convs()}
    assert convs["stem"] == (3, 64, 7, 112)
    assert convs["stage1.0.conv1"] == (64, 64, 1, 56)
    assert convs["stage1.0.down"] == (64, 256, 1, 56)
    # the zoo's v1 puts the stride on the first 1x1: stage 2 runs at 28
    assert convs["stage2.0.conv1"] == (256, 128, 1, 28)
    assert convs["stage4.2.conv3"] == (512, 2048, 1, 7)
    assert convs["fc"] == (2048, 1000, 0, 1)
    assert len(convs) == 1 + 16 * 3 + 4 + 1
    stem = 2 * 3 * 64 * 49 * 112 * 112             # 236,027,904
    assert stem == 236027904
    total = w.resnet50_forward_flops_per_image()
    # 3.86 G multiply-adds: the v1 (not v1.5) count
    assert total == pytest.approx(2 * 3.858e9, rel=2e-3)
    assert w.resnet50_train_flops_per_image() == 3 * total


def test_serve_flops_and_unknown_device():
    assert w.lm_serve_flops(PYTHIA14, 10) == 20 * w.lm_matmul_params(PYTHIA14)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("steps_per_s,limit", [(1 / 1.35, 100.0)])
def test_a_share_of_the_peak_cannot_pass_100(steps_per_s, limit):
    # at the chip's own peak a step of 8192 tokens takes 0.352 s
    step = w.lm_train_flops_per_step(PYTHIA14, 4, 2048)
    assert step / V5E["bf16_flops"] == pytest.approx(0.3522, rel=1e-3)
    mfu = 100.0 * step * steps_per_s / V5E["bf16_flops"]
    assert 20 < mfu < limit
