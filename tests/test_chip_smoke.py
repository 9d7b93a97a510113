"""chip_smoke.py: its leg functions at toy size on the CPU (so they cannot
rot between chip runs), and its exit contract — no TPU, or a failed leg,
means a non-zero exit and no result line; a passed run ends on exactly
``{"ok": true, "device": {...}}``."""
import json
import os
import subprocess
import sys

import jax
import pytest

import mxnet_tpu as mx

from conftest import subprocess_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
try:
    import chip_smoke
finally:
    sys.path.remove(REPO)

# dense_attn_max_score_mb=0: flash at every size, as the full-width legs
TOY_LM = dict(vocab_size=96, d_model=32, n_heads=2, n_layers=1, d_ff=64,
              max_len=64, dtype="float32", remat=False,
              dense_attn_max_score_mb=0)
TOY_HYBRID = dict(TOY_LM, n_layers=2, n_kv_heads=1, mlp="swiglu",
                  tie_embeddings=True, layer_types=("mamba", "attention"),
                  ssm_state=8, ssm_dt_rank=4)


def test_leg_trainer_toy():
    out = chip_smoke.leg_trainer(mx.cpu(), model_name="resnet18_v1",
                                 batch=4, size=32, classes=10, warmup=2,
                                 steps=3)
    assert out["recompiles_after_warmup"] == 0
    assert out["donated_bytes"] > 0
    assert out["loss_final"] < out["loss_after_warmup"]


def test_leg_lm_train_and_multichip_toy(monkeypatch):
    """Leg B's train step with the real kernels through the interpreter,
    then leg D on four virtual devices against leg B's first loss."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    out = chip_smoke.leg_lm_train(TOY_LM, batch=2, seq=32, steps=2,
                                  expect_impl="interpret")
    assert out["selected"] == {"flash_attention": {"interpret": 1},
                               "fused_rmsnorm": {"interpret": 3},
                               "fused_softmax_xent": {"interpret": 1}}
    hybrid = chip_smoke.leg_lm_train(TOY_HYBRID, batch=2, seq=32, steps=2,
                                     expect_impl="interpret")
    assert hybrid["selected"] == {"flash_attention": {"interpret": 1},
                                  "fused_rmsnorm": {"interpret": 5},
                                  "fused_softmax_xent": {"interpret": 1},
                                  "selective_scan": {"interpret": 1}}
    # a leg that says its attention stays dense by the model's own gate
    # (the full-width wide LM) counts no flash selection, and one that says
    # so of a model that takes flash fails
    dense = chip_smoke.leg_lm_train(dict(TOY_LM, dense_attn_max_score_mb=768),
                                    batch=2, seq=32, steps=2,
                                    expect_impl="interpret", flash=False)
    assert dense["selected"] == {"fused_rmsnorm": {"interpret": 3},
                                 "fused_softmax_xent": {"interpret": 1}}
    with pytest.raises(chip_smoke.SmokeFailure, match="expects dense"):
        chip_smoke.leg_lm_train(TOY_LM, batch=2, seq=32, steps=2,
                                expect_impl="interpret", flash=False)
    mesh = chip_smoke.leg_multichip(TOY_LM, batch=2, seq=32,
                                    ref_loss=out["losses"][0],
                                    devices=jax.devices()[:4])
    assert mesh["param_devices"] == 4
    # under a mesh kernel_impl must not offer the unpartitionable kernels
    assert mesh["selected"] == {"flash_attention": {"fallback": 1},
                                "fused_rmsnorm": {"fallback": 3},
                                "fused_softmax_xent": {"fallback": 1},
                                "selective_scan": {}}


def test_leg_kernel_parity_toy():
    out = chip_smoke.leg_kernel_parity(
        interpret=True, seqs=(24,), head_dims=(8,), cell_shape=(2, 48, 2, 8),
        cross_seqs=(16, 40), norm_shape=(2, 8, 32),
        xent_rows=8, vocab=100, mm_shapes=((40, 24, 72),),
        scan_shapes=((2, 40, 128, 8),), scan_cell_shape=(1, 48, 256, 16),
        mla_shape=(1, 40, 2, 24, 16),
        gmm_shapes=((50, 24, 16, (12, 0, 21, 9)),),
        gmm_cell_shape=(64, 32, 24, (20, 30)))
    assert out["checks"] > 56 + 4 + 6


def test_leg_server_toy():
    out = chip_smoke.leg_server(TOY_LM, n_requests=4, prompt_lens=(4, 30),
                                max_new=6, max_seq_len=64,
                                prefill_buckets="32", max_slots=2,
                                page_size=8)
    assert out["recompiles_after_warm"] == 0
    assert out["pages_used_after_drain"] == 0
    ref = chip_smoke.leg_server_reference(TOY_LM, prompt_len=20, max_new=8,
                                          page_size=8)
    assert ref["tokens_equal"] and ref["pages"] >= 2


def _run(argv):
    return subprocess.run([sys.executable] + argv, env=subprocess_env(),
                          cwd=REPO, capture_output=True, text=True,
                          timeout=240)


def test_chip_smoke_needs_a_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr, r.stderr[-2000:]
    assert '"ok"' not in r.stdout


def test_chip_smoke_failed_leg_exits_nonzero():
    child = (
        "import chip_smoke\n"
        "chip_smoke.preflight = lambda: {'device': {'platform': 'none'}}\n"
        "def boom(*args, **kwargs):\n"
        "    raise RuntimeError('leg A exploded')\n"
        "chip_smoke.leg_trainer = boom\n"
        "chip_smoke.main()\n"
    )
    r = _run(["-c", child])
    assert r.returncode != 0
    assert "leg A exploded" in r.stderr, r.stderr[-2000:]
    assert '"ok"' not in r.stdout


def test_chip_smoke_last_line_is_the_verdict_and_the_device_only():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    child = (
        "import chip_smoke\n"
        "chip_smoke.preflight = lambda: {'device': %r, 'versions': {}}\n"
        "def leg(*args, **kwargs):\n"
        "    return {'compile_s': 1.0, 'losses': [1.0]}\n"
        "for name in dir(chip_smoke):\n"
        "    if name.startswith('leg_'):\n"
        "        setattr(chip_smoke, name, leg)\n"
        "chip_smoke.main()\n" % (device,)
    )
    r = _run(["-c", child])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    # the per-leg record is printed, but not as the last line
    record = [ln for ln in lines if ln.startswith("[chip_smoke] record: ")]
    assert len(record) == 1
    assert "A_trainer" in json.loads(record[0].split("record: ", 1)[1])["legs"]
