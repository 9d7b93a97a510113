"""``TransformerLM`` with query heads set by layer (``attn_heads``), a
per-head output gate, a rotary term of each layer's own with a partial YaRN
part, a window, a dense leading layer and expert layers with a shared expert
and a routed scale -- against the benchmark's plain reference
(``benchmarks/harness/ref_gated_swa_moe_lm.py``, which imports nothing of
the program): loss, every gradient leaf, three steps; the rotary tables of
a partial YaRN setting against the formula written out; the runs the layer
loop is cut into by head count; the share of an expert-parallel layer tied
to the uncut layer; the new keys' defaults; what is refused by name."""
import argparse
import importlib
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerConfig, TransformerLM
from mxnet_tpu.models import rope as rope_module
from mxnet_tpu.models.transformer import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
from harness import cells  # noqa: E402
from harness import ref_gated_swa_moe_lm as ref  # noqa: E402
from harness import weights_gated_swa_moe_lm as bench_weights  # noqa: E402

CELL = "laguna_s21_train_s8k"
with open(os.path.join(REPO, "benchmarks", "configs",
                       "laguna-s2.1-l5-e16.json")) as _f:
    CONFIG = json.load(_f)
FULL = CONFIG["model"]
# the configuration's rehearsal sizes: 4 query heads of 16 on the full
# layers, 6 on the window layers (16 wide), 2 key/value heads, layers
# [full dense, window moe x 3, full moe], 16 experts of which 4 held, top-4,
# the published rotary settings (8 of 16 columns turn on the full layers),
# float32
TOY = dict(FULL, **CONFIG["rehearsal"]["model"])
YARN = TOY["attn_rope"][0]


def tokens(batch=2, seq=40, vocab=512, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              vocab)


def counters(prefix):
    return {k: v for k, v in telemetry.registry().snapshot()["counters"
                                                            ].items()
            if k.startswith(prefix)}


def test_the_toy_is_the_published_layer_at_toy_widths():
    cfg = TransformerConfig(**TOY)
    assert cfg.attn_heads == (4, 6, 6, 6, 4)
    assert cfg.attn_windows == (0, 16, 16, 16, 0)
    assert cfg.attn_gate == "per_head" and cfg.moe_routed_scale == 2.5
    assert cfg.attn_rope[0] == rope_module.RopeSetting(**YARN)
    assert cfg.attn_rope[0].dims(16) == 8 and cfg.attn_rope[1].dims(16) == 16
    assert cfg.mlp_types == ("dense", "moe", "moe", "moe", "moe")
    # one stack a head count, each over its own layers
    assert cfg.attn_stacks() == [("attention4", "attn4.", 4, 2),
                                 ("attention6", "attn6.", 6, 3)]
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0))
    want = bench_weights.leaf_shapes(TOY)
    assert {k: v.shape for k, v in shapes.items()} == {
        k: shape for k, (shape, _f) in want.items()}
    assert shapes["attn4.wqkv"].shape == (2, 64, (4 + 2 + 2) * 16)
    assert shapes["attn6.wo"].shape == (3, 6 * 16, 64)
    assert shapes["attn6.head_gate"].shape == (3, 64, 6)
    assert not any(k.startswith("attn.") for k in shapes)
    # the published widths give the count the configuration file states
    full = jax.eval_shape(TransformerLM(TransformerConfig(**FULL)).init,
                          jax.random.PRNGKey(0))
    assert full["attn72.wqkv"].shape == (3, 3072, 11264)
    assert full["attn48.wo"].shape == (2, 6144, 3072)
    assert sum(int(np.prod(s.shape)) for s in full.values()) == \
        CONFIG["assumed"]["parameters"] == bench_weights.param_count(FULL) \
        == 1113007104


def test_the_new_keys_default_to_what_was():
    cfg = TransformerConfig()
    assert (cfg.attn_heads, cfg.attn_gate, cfg.moe_routed_scale) == (
        (), "none", 1.0)
    assert cfg.rope == rope_module.RopeSetting()
    assert cfg.attn_stacks() == [("attention", "blocks.", 8, 4)]
    p = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        use_moe=True, n_experts=4)).init(jax.random.PRNGKey(0))
    assert not any("head_gate" in k or k.startswith("attn") for k in p)
    assert p["blocks.wqkv"].shape == (2, 32, 96)


@pytest.mark.parametrize("mode", ["lax", "kernels"])
def test_loss_and_every_gradient_leaf_are_the_references(mode, monkeypatch):
    """The whole toy model against the reference's loss and gradients.
    ``kernels`` runs flash (the window and the causal triangle), the grouped
    product, the row gathers, rmsnorm and the cross-entropy through the
    Pallas interpreter (the dense gate at 0)."""
    over = {}
    if mode == "kernels":
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
        over = dict(dense_attn_max_score_mb=0)
    model = TransformerLM(TransformerConfig(**dict(TOY, **over)))
    p = bench_weights.init(TOY, 11)
    t = tokens()
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(model.loss))(
            p, t[:, :-1], t[:, 1:])
        want, g_want = jax.jit(jax.value_and_grad(
            lambda q: ref.forward_loss(TOY, q, t)))(p)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert set(g_got) == set(g_want) == set(p)
    for name in sorted(g_want):
        scale = float(jnp.abs(g_want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_three_train_steps_are_the_references(monkeypatch):
    """Three steps of ``make_train_step`` against the reference trainer:
    each loss, and each leaf's change."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    model = TransformerLM(TransformerConfig(**dict(
        TOY, dense_attn_max_score_mb=0)))
    p = bench_weights.init(TOY, 2)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    opt = {"lr": 0.05, "momentum": 0.9}
    want = ref.TrainReference(TOY, p, opt)
    step = jax.jit(make_train_step(model, **opt))
    with jax.default_matmul_precision("highest"):
        for s in (1, 2, 3):
            t = np.asarray(tokens(seed=s))
            p, v, loss = step(p, v, t[:, :-1], t[:, 1:])
            assert float(loss) == pytest.approx(want.step(t), rel=5e-6)
    change = want.change_norms(lambda name: bench_weights.init_leaf(
        TOY, 2, name))
    assert set(change) == set(p)
    for name, norm in change.items():
        got = float(jnp.linalg.norm((p[name] - bench_weights.init_leaf(
            TOY, 2, name)).ravel()))
        assert got == pytest.approx(norm, rel=2e-4), name


def _yarn_written_out(dim, theta, factor, orig, beta_fast, beta_slow, t):
    """YaRN's inverse frequencies and cos as the published recipe writes
    them, a pair at a time in plain Python floats."""
    def pair(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    inv = []
    for i in range(dim // 2):
        extra = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        inv.append(extra / factor * ramp + extra * (1 - ramp))
    return inv, low, high


def test_rope_tables_of_a_partial_yarn_setting_are_the_formula():
    """The published full layers' setting over heads of 128: 64 columns
    turn, with YaRN's frequencies over a part 64 wide, cos and sin times
    the published attention factor, YaRN's own temperature; the other 64
    pass through."""
    setting = rope_module.RopeSetting(**FULL["attn_rope"][0])
    rot = setting.dims(128)
    assert rot == 64
    cos, sin = rope_module.rope_tables(setting, rot, 300)
    assert cos.shape == (300, 64)
    inv, low, high = _yarn_written_out(64, 5e5, 128, 8192, 32, 1, 300)
    assert (low, high) == (9, 18)
    af = 1.4852030263919618
    assert af == pytest.approx(0.1 * math.log(128) + 1, rel=1e-15)
    for t in (0, 1, 7, 299):
        for i in (0, 5, 9, 13, 18, 31):
            want = math.cos(t * inv[i]) * af
            assert float(cos[t, i]) == pytest.approx(want, abs=2e-6)
            assert float(cos[t, i + 32]) == pytest.approx(want, abs=2e-6)
            assert float(sin[t, i]) == pytest.approx(
                math.sin(t * inv[i]) * af, abs=2e-6)
    # the benchmark's reference makes the same tables
    r_cos, r_sin = ref.rope_tables(FULL["attn_rope"][0], 128, 300)
    np.testing.assert_allclose(r_cos, cos, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(r_sin, sin, rtol=1e-6, atol=1e-6)
    # the turn: the leading 64 columns rotate-half among themselves, the
    # rest untouched
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 300, 3, 128))
    y = rope_module.rotate_half(x, cos, sin)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    a, b = x[..., :32], x[..., 32:64]
    np.testing.assert_allclose(
        y[..., :32], a * cos[None, :, None, :32] - b * sin[None, :, None, :32],
        rtol=1e-5, atol=1e-5)
    # a plain setting turns the whole head at its own theta, unscaled
    plain = rope_module.RopeSetting(theta=1e4)
    cos, sin = rope_module.rope_tables(plain, plain.dims(128), 5)
    assert float(cos[4, 1]) == pytest.approx(
        math.cos(4 * 1e4 ** (-2 / 128)), abs=1e-6)
    assert float(jnp.max(jnp.abs(cos))) <= 1.0


def test_layer_runs_are_cut_by_head_count():
    """Published layers 0-4: three runs, each full layer indexing its place
    in the 4-head stack, the window layers theirs in the 6-head stack; the
    trace counts heads, gate, rotary kind and the routed scale."""
    cfg = TransformerConfig(**TOY)
    yarn, plain = cfg.attn_rope[0], cfg.attn_rope[1]
    first = ("attention", (0, yarn, 4), "dense")
    window = ("attention", (16, plain, 6), "moe")
    last = ("attention", (0, yarn, 4), "moe")
    assert cfg.layer_runs() == [
        (first, 0, 1, {"attention4": (0, 1), "dense": (0, 1)}),
        (window, 1, 4, {"attention6": (0, 3), "moe": (0, 3)}),
        (last, 4, 5, {"attention4": (1, 2), "moe": (3, 4)})]
    model = TransformerLM(cfg)
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    t = jax.ShapeDtypeStruct((2, 40), jnp.int32)
    before = {**counters("lm."), **counters("moe.routed_scale.")}
    jaxpr = jax.make_jaxpr(model.loss)(p, t, t).jaxpr
    moved = {k: v - before.get(k, 0)
             for k, v in {**counters("lm."),
                          **counters("moe.routed_scale.")}.items()
             if v != before.get(k, 0)}
    assert moved == {"lm.layers.dense.1x1": 1, "lm.layers.moe.3x3": 1,
                     "lm.layers.moe.1x1": 1,
                     "lm.attn.full.rope.1": 2, "lm.attn.window.rope.3": 1,
                     "lm.attn.heads.4.1": 2, "lm.attn.heads.6.3": 1,
                     "lm.attn.gate.per_head.1": 2,
                     "lm.attn.gate.per_head.3": 1,
                     "lm.rope.full.8of16.yarn": 2,
                     "lm.rope.window.16of16.plain": 3,
                     # where an expert layer's body is traced: two bodies
                     "moe.routed_scale.2.5": 2}
    assert [(e.params["length"], e.params["unroll"]) for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [(1, 1), (3, 3), (1, 1)]
    # the family hands them to the benchmark's readings
    family = importlib.import_module("harness.family_gated_swa_moe_lm")
    assert set(moved) <= set(family.program_counters())


def test_the_gate_scales_each_head_by_its_sigmoid():
    """A gate of zeros halves every head; a gate that reads one feature
    scales each head by its own sigmoid of it."""
    model = TransformerLM(TransformerConfig(**TOY))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64))
    o = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 6, 16))
    half = model._head_gate({"head_gate": jnp.zeros((64, 6))}, h, o)
    np.testing.assert_allclose(half, 0.5 * o, rtol=1e-6)
    w = jnp.zeros((64, 6)).at[0].set(jnp.arange(6.0))
    gated = model._head_gate({"head_gate": w}, h, o)
    want = jax.nn.sigmoid(h[..., 0, None] * jnp.arange(6.0))[..., None] * o
    np.testing.assert_allclose(gated, want, rtol=1e-5, atol=1e-6)


def test_the_routed_scale_multiplies_the_routed_part_alone():
    """``moe_routed_scale`` times the held experts' weighted sum; the
    shared expert is added as it is."""
    m = dict(TOY, experts_held=list(range(16)))
    p = bench_weights.init(m, 5)
    lp = ref.layer_leaves(p, ref.kinds_of(m), 1)
    g = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))
    out = {}
    for scale in (1.0, 2.5):
        model = TransformerLM(TransformerConfig(**dict(
            m, moe_routed_scale=scale)))
        with jax.default_matmul_precision("highest"):
            out[scale] = model._experts(lp, g)[0]
            shared = model._mlp(g, lp["shared_up"], lp["shared_down"],
                                lp["shared_gate"])
    np.testing.assert_allclose(out[2.5] - shared, 2.5 * (out[1.0] - shared),
                               rtol=1e-4, atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The expert layer at toy widths, 16 experts, top-4, the routed scale
    and the shared expert: the shares holding experts 0-3, 4-7, 8-11 and
    12-15 each give their own experts' terms plus the shared expert; with
    the shared expert counted once they add up to the reference layer that
    holds all 16, and each (token, slot) pair lands on exactly one share."""
    m = dict(TOY, experts_held=list(range(16)))
    p = bench_weights.init(m, 21)
    lp = ref.layer_leaves(p, ref.kinds_of(m), 1)
    g = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))   # rms2(x')
    q_ = lambda a: a                                            # noqa: E731
    with jax.default_matmul_precision("highest"):
        weights, experts, aux_whole = ref.route(lp, g, m)
        weights = weights * m["moe_routed_scale"]
        shared = ref.gated_mlp(g, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"], q_)
        whole = ref.held_experts(lp, g, weights, experts, list(range(16)),
                                 q_) + shared
        parts, held_pairs = [], 0.0
        for share in ((0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11),
                      (12, 13, 14, 15)):
            model = TransformerLM(TransformerConfig(
                **dict(m, experts_held=share)))
            bp = dict(lp, **{k: lp[k][jnp.asarray(share)] for k in
                             ("moe_gate", "moe_up", "moe_down")})
            ff, aux = model._experts(bp, g)
            assert float(aux[0]) == pytest.approx(float(aux_whole),
                                                  rel=1e-6)
            held_pairs += float(aux[1])
            part = ref.held_experts(bp, g, weights, experts, list(share), q_)
            np.testing.assert_allclose(ff, part + shared, rtol=1e-4,
                                       atol=1e-5)
            parts.append(ff)
    assert held_pairs == 2 * 24 * 4
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, rtol=1e-4,
                               atol=1e-5)


def drive(readings):
    cell = cells.Cell(cells.load_benchmark(), CELL)
    cell.rehearse()
    args = argparse.Namespace(seed=17, seconds=0.3, trace=0, rehearse=True,
                              readings=readings)
    kind = importlib.import_module("harness.kind_" + cell.traffic["kind"])
    return kind.run(cell, args, jax.devices()[:1], time.perf_counter())


@pytest.fixture(scope="module")
def readings():
    """Every planted reading in one drive: the sound reference is followed
    once."""
    return drive(",".join(["control", *ref.FAULTS]))["compared"]


@pytest.mark.parametrize("what", ["control", *ref.FAULTS])
def test_the_control_and_each_planted_fault_fail(readings, what):
    """The reference one precision down, with half the tokens, without the
    output gate, turning whole heads on the full layers, or with the routed
    sum unscaled, put in the program's place: not correct, by the loss and
    by the first gradient."""
    over = {k for k, v in readings[what].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert {"loss1_rel", "grad1_norm_gap"} <= over, readings[what]


def test_the_reference_stays_out_of_a_capped_compile_cache(monkeypatch):
    """Under a size cap the reference's programs are not written to the
    persistent cache (they would evict the step); without one they are,
    and the threshold is put back either way."""
    family = importlib.import_module("harness.family_gated_swa_moe_lm")
    seen = []
    monkeypatch.setattr(family, "_reference_readings", lambda *a: seen.append(
        jax.config.jax_persistent_cache_min_compile_time_secs))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    cap = jax.config.jax_compilation_cache_max_size
    try:
        for size in (-1, 192 << 20):
            jax.config.update("jax_compilation_cache_max_size", size)
            family.train_reference_readings(CONFIG, None, 0, None, None)
    finally:
        jax.config.update("jax_compilation_cache_max_size", cap)
    assert seen == [before, math.inf]
    assert jax.config.jax_persistent_cache_min_compile_time_secs == before


def test_an_unknown_fault_is_refused_by_name():
    with pytest.raises(AssertionError, match="no fault 'no_window'"):
        ref.TrainReference(TOY, bench_weights.init(TOY, 1),
                           {"lr": 0.01, "momentum": 0.9}, fault="no_window")


# -- refused by name ----------------------------------------------------------
DENSE = dict(vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
             max_len=64, dtype="float32")


@pytest.mark.parametrize("over", [dict(attn_heads=[4, 8]),
                                  dict(attn_gate="per_head")])
def test_serving_refuses_head_counts_by_layer_and_the_gate_by_name(over):
    model = TransformerLM(TransformerConfig(**dict(DENSE, **over)))
    for call in (model._refuse_serving, lambda: model.init_kv_pages(4, 8)):
        with pytest.raises(NotImplementedError, match="head count"):
            call()


@pytest.mark.parametrize("bad", [
    dict(attn_heads=[4]),                             # names 1 layer of 2
    dict(attn_heads=[4, 6], n_kv_heads=4),            # 6 on 4 kv heads
    dict(attn_heads=[4, 8], layer_types=["attention", "mamba"]),
    dict(attn_gate="per_token"),
    dict(attn_gate="per_head", attention="mla", kv_lora_rank=16,
         qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8),
    dict(moe_routed_scale=0.0),
    dict(attn_rope=[{"theta": 1e4, "fraction": 0.0}, 0]),
])
def test_a_configuration_that_cannot_be_built_is_refused(bad):
    with pytest.raises(AssertionError):
        TransformerConfig(**dict(DENSE, **bad))


def test_a_rotary_setting_with_an_unknown_key_or_an_odd_part_is_refused():
    with pytest.raises(TypeError):
        TransformerConfig(**dict(DENSE, attn_rope=[{"base": 1e4}, 0]))
    with pytest.raises(AssertionError, match="rotary part of 3 of 8"):
        rope_module.RopeSetting(fraction=0.375).dims(8)


def test_the_capacity_dispatch_over_ep_with_a_routed_scale_is_refused():
    from mxnet_tpu.parallel import make_mesh
    cfg = TransformerConfig(**dict(DENSE, use_moe=True, n_experts=4,
                                   moe_routed_scale=2.0))
    model = TransformerLM(cfg)
    bp = {k.split(".", 1)[1]: v[0] for k, v in
          model.init(jax.random.PRNGKey(0)).items()
          if k.startswith("blocks.")}
    with make_mesh(ep=2, devices=jax.devices()[:2]):
        with pytest.raises(AssertionError, match="routed scale"):
            model._experts(bp, jnp.zeros((1, 8, 32)))
