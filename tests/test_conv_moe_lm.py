"""``TransformerLM`` with gated short convolutions beside GQA attention with
q/k norms (``layer_types`` beside ``mlp_types``), a sigmoid router whose
selection bias the step moves from its own load, and a tied head -- against
the benchmark's plain reference (``benchmarks/harness/ref_conv_moe_lm.py``,
which imports nothing of the program): loss, every gradient leaf, three
steps with the bias moving; the share of an expert-parallel layer tied to
the uncut layer; the runs the layer loop is cut into; the new keys'
defaults; what is refused by name."""
import argparse
import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerConfig, TransformerLM
from mxnet_tpu.models.transformer import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
from harness import cells  # noqa: E402
from harness import ref_conv_moe_lm as ref  # noqa: E402
from harness import weights_conv_moe_lm as bench_weights  # noqa: E402

CELL = "lfm2_8b_train_s8k"
with open(os.path.join(REPO, "benchmarks", "configs",
                       "lfm2-8b-a1b-l5-e8.json")) as _f:
    CONFIG = json.load(_f)
FULL = CONFIG["model"]
# the configuration's rehearsal sizes: 4 heads of 16 on 2 key/value heads at
# d_model 64, layers [conv dense, attention moe, conv moe x 3], 8 experts of
# which 4 held, top-2, float32
TOY = dict(FULL, **CONFIG["rehearsal"]["model"])


def tokens(batch=2, seq=40, vocab=512, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              vocab)


def counters(prefix):
    return {k: v for k, v in telemetry.registry().snapshot()["counters"
                                                            ].items()
            if k.startswith(prefix)}


def biased(p, seed=3, scale=0.05):
    """The weights with a bias that chooses: a step from zero moves it by
    the rate alone, and the router's choice would hardly see it."""
    return dict(p, **{"moe.expert_bias": scale * jax.random.normal(
        jax.random.PRNGKey(seed), p["moe.expert_bias"].shape)})


def test_the_toy_is_the_published_layer_at_toy_widths():
    cfg = TransformerConfig(**TOY)
    assert cfg.layer_types == ("conv", "attention", "conv", "conv", "conv")
    assert cfg.mlp_types == ("dense", "moe", "moe", "moe", "moe")
    assert cfg.qk_norm and cfg.norm_eps == 1e-5 and cfg.short_conv == 3
    assert cfg.moe_router == "sigmoid" and cfg.moe_expert_bias
    assert cfg.tie_embeddings and cfg.moe_aux_weight == 0
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0))
    want = bench_weights.leaf_shapes(TOY)
    assert {k: v.shape for k, v in shapes.items()} == {
        k: shape for k, (shape, _f) in want.items()}
    assert shapes["moe.expert_bias"].dtype == jnp.float32
    assert shapes["sconv.in_proj"].shape == (4, 64, 192)
    assert shapes["attn.q_norm_scale"].shape == (1, 16)
    # the published widths give the count the configuration file states
    full = jax.eval_shape(TransformerLM(TransformerConfig(**FULL)).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in full.values()) == \
        CONFIG["assumed"]["parameters"] == bench_weights.param_count(FULL) \
        == 507820288


def test_the_new_keys_default_to_what_was():
    cfg = TransformerConfig()
    assert (cfg.qk_norm, cfg.norm_eps, cfg.moe_router, cfg.moe_expert_bias,
            cfg.short_conv) == (False, 1e-6, "softmax", False, 3)
    p = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        use_moe=True, n_experts=4)).init(jax.random.PRNGKey(0))
    assert not any("norm_scale" in k or "bias" in k or k.startswith("sconv")
                   for k in p)


@pytest.mark.parametrize("mode", ["lax", "kernels"])
def test_loss_and_every_gradient_leaf_are_the_references(mode, monkeypatch):
    """The whole toy model against the reference's loss, its load and its
    gradients, with a bias that changes the choice.  ``kernels`` runs the
    gated convolution, flash, the grouped product, rmsnorm and the
    cross-entropy through the Pallas interpreter (the dense gate at 0)."""
    over = {}
    if mode == "kernels":
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
        over = dict(dense_attn_max_score_mb=0)
    model = TransformerLM(TransformerConfig(**dict(TOY, **over)))
    p = biased(bench_weights.init(TOY, 11))
    t = tokens()
    before = counters("pallas.select.short_conv.")
    with jax.default_matmul_precision("highest"):
        (got, load), g_got = jax.jit(jax.value_and_grad(
            model.loss_and_load, has_aux=True))(p, t[:, :-1], t[:, 1:])
        (want, loads), g_want = jax.jit(jax.value_and_grad(
            lambda q: ref.forward_loss(TOY, q, t), has_aux=True))(p)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    # the load is a count: equal, not near
    np.testing.assert_array_equal(load, jnp.stack(loads))
    assert load.shape == (4, 8) and float(load.sum()) == 4 * 2 * 40 * 2
    assert set(g_got) == set(g_want)
    for name in sorted(g_want):
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)
    assert not np.any(g_got["moe.expert_bias"])
    moved = {k: v - before.get(k, 0)
             for k, v in counters("pallas.select.short_conv.").items()
             if v != before.get(k, 0)}
    # two runs of conv layers, each traced once
    assert moved == {"pallas.select.short_conv." + (
        "interpret" if mode == "kernels" else "fallback"): 2}


def test_three_train_steps_with_the_bias_moving_are_the_references(
        monkeypatch):
    """Three steps of ``make_train_step`` against the reference trainer:
    each loss, each leaf's change, and the bias to the bit -- it moved by
    the sign rule alone, its momentum stayed zero."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    m = dict(TOY, dense_attn_max_score_mb=0)
    model = TransformerLM(TransformerConfig(**m))
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
    rate = TOY["moe_bias_rate"]
    moves = np.asarray(p["moe.expert_bias"]) / rate
    assert set(np.round(moves).ravel()) <= {-3, -1, 1, 3, -2, 0, 2}
    assert np.allclose(moves, np.round(moves), atol=1e-4) and np.any(moves)
    assert not np.any(v["moe.expert_bias"])
    ref_bias = np.stack([lp["expert_bias"] for lp in want.layers
                         if "expert_bias" in lp])
    np.testing.assert_array_equal(p["moe.expert_bias"], ref_bias)
    change = want.change_norms(lambda name: bench_weights.init_leaf(
        TOY, 2, name))
    assert set(change) == set(p)
    for name, norm in change.items():
        got = float(jnp.linalg.norm((p[name] - bench_weights.init_leaf(
            TOY, 2, name)).ravel()))
        assert got == pytest.approx(norm, rel=2e-4), name


def test_the_bias_moves_by_the_sign_of_the_steps_own_load():
    """One step: the bias of each layer moves by ``rate * sign(mean(c) -
    c)``, ``c`` the load ``loss_and_load`` gives for the step's tokens; the
    other leaves take SGD's update."""
    model = TransformerLM(TransformerConfig(**TOY))
    p = biased(bench_weights.init(TOY, 4))
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    t = tokens(seed=5)
    _, load = model.loss_and_load(p, t[:, :-1], t[:, 1:])
    new_p, new_v, _ = jax.jit(make_train_step(model, lr=0.1, momentum=0.9))(
        p, v, t[:, :-1], t[:, 1:])
    want = p["moe.expert_bias"] + TOY["moe_bias_rate"] * jnp.sign(
        load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(new_p["moe.expert_bias"], want, rtol=0,
                               atol=1e-7)
    assert not np.any(new_v["moe.expert_bias"])
    np.testing.assert_allclose(new_p["sconv.in_proj"],
                               p["sconv.in_proj"] - 0.1 * new_v[
                                   "sconv.in_proj"], rtol=1e-6, atol=1e-7)


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
    """The reference one precision down, with half the tokens, with softmax
    scores, choosing without its bias, without the q/k norms, or with every
    tap of the convolution a step late, put in the program's place: not
    correct.  ``no_expert_bias`` is the start's router on step 1 (the bias
    is zero there): steps 2 and 3 and the change tell it."""
    over = {k for k, v in readings[what].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    want = ({"loss2_rel", "change3_norm_gap"} if what == "no_expert_bias"
            else {"loss1_rel", "grad1_norm_gap"})
    assert want & over, readings[what]


def test_an_unknown_fault_is_refused_by_name():
    with pytest.raises(AssertionError, match="no fault 'no_window'"):
        ref.TrainReference(TOY, bench_weights.init(TOY, 1),
                           {"lr": 0.01, "momentum": 0.9}, fault="no_window")


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """LFM2's expert layer at toy widths, 8 experts, top-3, sigmoid scores
    and a bias that changes the choice: the shares holding experts 0-1, 2-3,
    4-5 and 6-7 each give their own experts' terms; they add up to the
    reference layer that holds all 8, every share computes the load alike
    (the bias update is the same on every chip), and each (token, slot) pair
    lands on exactly one share."""
    m = dict(TOY, moe_top_k=3, experts_held=list(range(8)))
    p = biased(bench_weights.init(m, 21), seed=8, scale=0.2)
    g = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))   # rms2(x')
    lp = ref.layer_leaves(p, ref.kinds_of(m), 1)
    with jax.default_matmul_precision("highest"):
        weights, experts, load_whole = ref.route(lp, g, m)
        whole = ref.held_experts(lp, g, weights, experts, list(range(8)),
                                 lambda a: a)
        parts, held_pairs = [], 0.0
        for share in ((0, 1), (2, 3), (4, 5), (6, 7)):
            model = TransformerLM(TransformerConfig(
                **dict(m, experts_held=share)))
            bp = dict(lp, **{k: lp[k][jnp.asarray(share)] for k in
                             ("moe_gate", "moe_up", "moe_down")})
            ff, aux = model._experts(bp, g)
            np.testing.assert_array_equal(aux[2:], load_whole)
            held_pairs += float(aux[1])
            parts.append(ff)
            part = ref.held_experts(bp, g, weights, experts, list(share),
                                    lambda a: a)
            np.testing.assert_allclose(ff, part, rtol=1e-4, atol=1e-5)
    assert held_pairs == 2 * 24 * 3 == float(load_whole.sum())
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    # the bias chose: without it the choice differs, and the weights are
    # the unbiased scores either way
    plain = ref.route(lp, g, m, fault="no_expert_bias")[1]
    assert not np.array_equal(np.asarray(plain), np.asarray(experts))


def test_sigmoid_weights_are_the_unbiased_scores_renormalised():
    from mxnet_tpu.parallel.moe import route
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.asarray([0.0, 5.0, 0.0, 0.0, 0.0, -5.0])
    weights, experts, _ = route(x, w, 2, True, scoring="sigmoid", bias=bias)
    s = jax.nn.sigmoid(x @ w)
    assert bool(jnp.all(jnp.any(experts == 1, axis=-1)))     # always chosen
    assert not bool(jnp.any(experts == 5))                   # never
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # no gradient reaches the bias
    g = jax.grad(lambda b: route(x, w, 2, True, scoring="sigmoid",
                                 bias=b)[0].sum())(bias)
    assert not np.any(g)


def test_layer_types_beside_mlp_types_are_cut_together():
    """Runs are cut on (mixer, setting, MLP): the published layers 1-5 are
    three runs, each indexing its mixer's and its MLP kind's own stacks."""
    cfg = TransformerConfig(**TOY)
    conv_dense = ("conv", (0, None, 4), "dense")
    attn_moe = ("attention", (0, cfg.rope, 4), "moe")
    conv_moe = ("conv", (0, None, 4), "moe")
    assert cfg.layer_runs() == [
        (conv_dense, 0, 1, {"conv": (0, 1), "dense": (0, 1)}),
        (attn_moe, 1, 2, {"attention": (0, 1), "moe": (0, 1)}),
        (conv_moe, 2, 5, {"conv": (1, 4), "moe": (1, 4)})]
    model = TransformerLM(cfg)
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    t = jax.ShapeDtypeStruct((2, 40), jnp.int32)
    before = counters("lm.")
    jaxpr = jax.make_jaxpr(model.loss)(p, t, t).jaxpr
    moved = {k: v - before.get(k, 0) for k, v in counters("lm.").items()
             if v != before.get(k, 0)}
    assert moved == {"lm.layers.conv.dense.1x1": 1,
                     "lm.layers.attention.moe.1x1": 1,
                     "lm.layers.conv.moe.3x3": 1,
                     "lm.attn.full.rope.1": 1,
                     "lm.rope.full.16of16.plain": 1}
    assert [(e.params["length"], e.params["unroll"]) for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [(1, 1), (1, 1), (3, 3)]


def test_qk_norm_is_over_each_head_before_the_rotary_term():
    """With unit scales, each head of the q and k flash is handed has an
    RMS of one before the rotation, and rotation keeps a vector's norm."""
    cfg = TransformerConfig(**TOY)
    model = TransformerLM(cfg)
    p = bench_weights.init(TOY, 3)
    bp = ref.layer_leaves(p, ref.kinds_of(TOY), 1)
    bp["q_norm_scale"] = 2.0 * bp["q_norm_scale"]
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    q, k, v = model._qkv(bp, h, rope=cfg.rope)
    rms = lambda a: jnp.sqrt(jnp.mean(a * a, axis=-1))
    np.testing.assert_allclose(rms(q), 2.0, rtol=1e-3)
    np.testing.assert_allclose(rms(k), 1.0, rtol=1e-3)
    assert float(jnp.abs(rms(v) - 1.0).max()) > 0.1


# -- refused by name ----------------------------------------------------------
DENSE = dict(vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
             max_len=64, dtype="float32")


@pytest.mark.parametrize("over,names", [
    (dict(layer_types=["conv", "attention"]), "short-convolution"),
    (dict(qk_norm=True), "q/k norms"),
    (dict(use_moe=True, n_experts=4, moe_router="sigmoid"),
     "sigmoid router"),
    (dict(use_moe=True, n_experts=4, moe_expert_bias=True), "expert bias"),
])
def test_serving_refuses_the_conv_mixer_qk_norms_and_the_router_by_name(
        over, names):
    model = TransformerLM(TransformerConfig(**dict(DENSE, **over)))
    with pytest.raises(NotImplementedError, match=names):
        model._refuse_serving()
    if not over.get("use_moe"):
        with pytest.raises(NotImplementedError, match=names):
            model.init_kv_pages(4, 8)


@pytest.mark.parametrize("bad", [
    dict(layer_types=["conv", "ssm"]),
    dict(attn_rope=[1, 0], layer_types=["conv", "attention"]),
    dict(moe_router="relu", use_moe=True, n_experts=4),
    dict(moe_expert_bias=True),                       # without experts
])
def test_a_configuration_that_cannot_be_built_is_refused(bad):
    with pytest.raises(AssertionError):
        TransformerConfig(**dict(DENSE, **bad))


def test_the_capacity_dispatch_over_ep_with_a_sigmoid_router_is_refused():
    from mxnet_tpu.parallel import make_mesh
    cfg = TransformerConfig(**dict(DENSE, use_moe=True, n_experts=4,
                                   moe_router="sigmoid"))
    model = TransformerLM(cfg)
    bp = {k.split(".", 1)[1]: v[0] for k, v in
          model.init(jax.random.PRNGKey(0)).items()
          if k.startswith("blocks.")}
    with make_mesh(ep=2, devices=jax.devices()[:2]):
        with pytest.raises(AssertionError, match="sigmoid router"):
            model._experts(bp, jnp.zeros((1, 8, 32)))
