"""``TransformerLM`` with window and full attention mixed by layer
(``attn_windows``, ``attn_rope``), a head width of its own, a router placed
before attention and ReGLU experts -- against the benchmark's plain
reference (``benchmarks/harness/ref_swa_moe_lm.py``, which imports nothing
of the program): loss, every gradient leaf, three steps; the share of an
expert-parallel layer tied to the uncut layer; the runs the layer loop is
cut into; what the positional term is and is not; what is refused by name."""
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
from harness import ref_swa_moe_lm as ref  # noqa: E402
from harness import weights_swa_moe_lm as bench_weights  # noqa: E402

CELL = "smallthinker_train_s16k"
with open(os.path.join(REPO, "benchmarks", "configs",
                       "smallthinker-21b-l4-e16.json")) as _f:
    CONFIG = json.load(_f)
FULL = CONFIG["model"]
# the configuration's rehearsal sizes: 4 heads of 32 on 2 key/value heads at
# d_model 64, layers [full, window 16 x 3], 8 experts of which 4 held,
# top-2, float32
TOY = dict(FULL, **CONFIG["rehearsal"]["model"])


def tokens(batch=2, seq=40, vocab=512, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              vocab)


def counters(prefix):
    return {k: v for k, v in telemetry.registry().snapshot()["counters"
                                                            ].items()
            if k.startswith(prefix)}


def block_leaves(p, i):
    return {k.split(".", 1)[1]: v[i] for k, v in p.items()
            if k.startswith("blocks.")}


def test_the_toy_is_the_published_layer_at_toy_widths():
    cfg = TransformerConfig(**TOY)
    assert cfg.n_heads * cfg.head_dim == 128 != cfg.d_model == 64
    assert cfg.attn_windows == (0, 16, 16, 16)
    assert cfg.attn_rope == (None, cfg.rope, cfg.rope, cfg.rope)
    assert cfg.mlp == "reglu" and cfg.moe_router_pre_attention
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    want = bench_weights.leaf_shapes(TOY)
    assert {k: v.shape for k, v in shapes.items()} == {
        k: shape for k, (shape, _f) in want.items()}
    assert shapes["blocks.wqkv"].shape == (4, 64, (4 + 2 + 2) * 32)
    assert shapes["blocks.wo"].shape == (4, 128, 64)
    # the published widths give the count the configuration file states
    full = jax.eval_shape(TransformerLM(TransformerConfig(**FULL)).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in full.values()) == \
        CONFIG["assumed"]["parameters"] == bench_weights.param_count(FULL)


@pytest.mark.parametrize("mode", ["lax", "kernels"])
def test_loss_and_every_gradient_leaf_are_the_references(mode, monkeypatch):
    """The whole toy model against the reference's loss and gradients.
    ``kernels`` runs flash with and without the window, the grouped product,
    rmsnorm and the cross-entropy through the Pallas interpreter (the dense
    gate at 0); 40 positions, so the window of 16 cuts."""
    over = {}
    if mode == "kernels":
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
        over = dict(dense_attn_max_score_mb=0)
    model = TransformerLM(TransformerConfig(**dict(TOY, **over)))
    p = bench_weights.init(TOY, 11)
    t = tokens()
    before = counters("pallas.flash.window.")
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(model.loss))(
            p, t[:, :-1], t[:, 1:])
        want, g_want = jax.jit(jax.value_and_grad(
            lambda q: ref.forward_loss(TOY, q, t)))(p)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert set(g_got) == set(g_want)
    for name in sorted(g_want):
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)
    moved = {k: v - before.get(k, 0)
             for k, v in counters("pallas.flash.window.").items()
             if v != before.get(k, 0)}      # other files' windows stand still
    if mode == "kernels":
        # one run of three window layers, traced once (the forward once
        # more as the rule's primal)
        assert moved == {"pallas.flash.window.fwd.16": 2,
                         "pallas.flash.window.dq.16": 1,
                         "pallas.flash.window.dkv.16": 1}
    else:
        assert not moved


def test_three_train_steps_are_the_references(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    model = TransformerLM(TransformerConfig(
        **dict(TOY, dense_attn_max_score_mb=0)))
    p = bench_weights.init(TOY, 2)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    opt = {"lr": 0.05, "momentum": 0.9}
    want = ref.TrainReference(TOY, p, opt)
    step = jax.jit(make_train_step(model, **opt))
    batches = [np.asarray(tokens(seed=s)) for s in (1, 2, 3)]
    with jax.default_matmul_precision("highest"):
        for t in batches:
            p, v, loss = step(p, v, t[:, :-1], t[:, 1:])
            assert float(loss) == pytest.approx(want.step(t), rel=5e-6)
    change = want.change_norms(lambda name: bench_weights.init_leaf(
        TOY, 2, name))
    for name, norm in change.items():
        got = float(jnp.linalg.norm((p[name] - bench_weights.init_leaf(
            TOY, 2, name)).ravel()))
        assert got == pytest.approx(norm, rel=2e-4), name


def drive(readings):
    cell = cells.Cell(cells.load_benchmark(), CELL)
    cell.rehearse()
    args = argparse.Namespace(seed=17, seconds=0.3, trace=0, rehearse=True,
                              readings=readings)
    kind = importlib.import_module("harness.kind_" + cell.traffic["kind"])
    return kind.run(cell, args, jax.devices()[:1], time.perf_counter())


@pytest.mark.parametrize("what", ["control", *ref.FAULTS])
def test_the_control_and_each_planted_fault_fail(what):
    """The reference one precision down, with half the tokens, with every
    layer attending over the whole prefix, or with the router reading the
    rows the experts are given, put in the program's place: not correct."""
    out = drive(what)
    assert out["correct"] is False
    over = {k for k, v in out["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert {"loss1_rel", "grad1_norm_gap"} & over, out["compared"]


def test_an_unknown_fault_is_refused_by_name():
    with pytest.raises(AssertionError, match="no fault 'no_exchange'"):
        ref.TrainReference(TOY, bench_weights.init(TOY, 1),
                           {"lr": 0.01, "momentum": 0.9}, fault="no_exchange")


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """SmallThinker's expert layer at toy widths, 8 experts, top-3, the
    router on the layer's un-normed input: the shares holding experts 0-1,
    2-3, 4-5 and 6-7 each give their own experts' terms; they add up to the
    reference layer that holds all 8, every share computes the balance term
    alike, and each (token, slot) pair lands on exactly one share."""
    m = dict(TOY, moe_top_k=3, experts_held=list(range(8)))
    p = bench_weights.init(m, 21)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))   # layer input
    g = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))   # rms2(x')
    lp = block_leaves(p, 1)
    with jax.default_matmul_precision("highest"):
        weights, experts, aux_whole = ref.route(lp, x, m)
        whole = ref.held_experts(lp, g, weights, experts, list(range(8)),
                                 lambda a: a)
        parts, held_pairs = [], 0.0
        for share in ((0, 1), (2, 3), (4, 5), (6, 7)):
            model = TransformerLM(TransformerConfig(
                **dict(m, experts_held=share)))
            bp = dict(lp, **{k: lp[k][jnp.asarray(share)] for k in
                             ("moe_gate", "moe_up", "moe_down")})
            ff, aux = model._experts(bp, g, model._route(bp, x))
            assert float(aux[0]) == pytest.approx(float(aux_whole), rel=1e-5)
            held_pairs += float(aux[1])
            parts.append(ff)
            # and the reference given the same share is that share
            part = ref.held_experts(bp, g, weights, experts, list(share),
                                    lambda a: a)
            np.testing.assert_allclose(ff, part, rtol=1e-4, atol=1e-5)
    assert held_pairs == 2 * 24 * 3
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    # the router read x, not the rows dispatched: routed on g the choice
    # differs
    assert not np.array_equal(np.asarray(ref.route(lp, g, m)[1]),
                              np.asarray(experts))


@pytest.mark.parametrize("periods", [1, 2])
def test_the_published_layout_is_cut_into_runs_of_one_and_three(periods):
    L = 4 * periods
    cfg = TransformerConfig(**dict(
        TOY, n_layers=L, attn_windows=[0, 16, 16, 16] * periods,
        attn_rope=[0, 1, 1, 1] * periods))
    full = ("attention", (0, None, 4), "moe")
    window = ("attention", (16, cfg.rope, 4), "moe")
    runs = cfg.layer_runs()
    assert [(key, lo, hi) for key, lo, hi, _own in runs] == [
        (key, 4 * i + lo, 4 * i + hi) for i in range(periods)
        for key, lo, hi in ((full, 0, 1), (window, 1, 4))]
    # every stack is indexed by the layer itself: no ``attn.`` / ``moe.``
    for _key, lo, hi, own in runs:
        assert own == {"attention": (lo, hi), "moe": (lo, hi)}
    model = TransformerLM(cfg)
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    t = jax.ShapeDtypeStruct((2, 40), jnp.int32)
    before = {**counters("lm."), **counters("moe.router.")}
    jaxpr = jax.make_jaxpr(model.loss)(p, t, t).jaxpr
    moved = {k: v - before.get(k, 0)
             for k, v in {**counters("lm."), **counters("moe.router.")}.items()
             if v != before.get(k, 0)}
    assert moved == {"lm.layers.attention.1x1": periods,
                     "lm.layers.attention.3x3": periods,
                     "lm.attn.full.nope.1": periods,
                     "lm.attn.window.rope.3": periods,
                     "lm.rope.window.32of32.plain": 3 * periods,
                     # counted where a body is traced: the second period's
                     # runs share the first one's two bodies
                     "moe.router.pre_attention": 2}
    assert [(e.params["length"], e.params["unroll"]) for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [(1, 1), (3, 3)] * periods


def test_equal_settings_everywhere_are_one_run_as_before():
    cfg = TransformerConfig(**dict(TOY, attn_windows=(), attn_rope=()))
    assert [(lo, hi) for _k, lo, hi, _o in cfg.layer_runs()] == [(0, 4)]
    cfg = TransformerConfig(**dict(TOY, attn_windows=[8] * 4,
                                   attn_rope=[1] * 4))
    assert [(k[1], lo, hi) for k, lo, hi, _o in cfg.layer_runs()] == [
        ((8, cfg.rope, 4), 0, 4)]


def _mixer(window, rope, T):
    model = TransformerLM(TransformerConfig(**TOY))
    bp = block_leaves(bench_weights.init(TOY, 3), 1)
    return lambda h: model._self_attention(
        bp, h, window=window, rope=model.cfg.rope if rope else None)[0]


def test_a_window_layers_term_is_relative_and_a_full_layer_has_none():
    """A window layer (rotary q and k): shift every position by a constant
    and a query whose window lies inside the shifted part reads the same.
    A full layer (no positional term): a query's output does not change
    when the positions before it are permuted; with a rotary term it does."""
    W, c, T = 16, 7, 40
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, 64))
    junk = jax.random.normal(jax.random.PRNGKey(6), (1, c, 64))
    with jax.default_matmul_precision("highest"):
        here = _mixer(W, True, T)(h)
        shifted = _mixer(W, True, T + c)(jnp.concatenate([junk, h], axis=1))
        np.testing.assert_allclose(shifted[:, c + W - 1:], here[:, W - 1:],
                                   rtol=1e-4, atol=1e-5)
        # a query that still sees the junk differs
        assert float(jnp.abs(shifted[:, c] - here[:, 0]).max()) > 1e-3
        # without the window the shift is seen by everyone
        assert float(jnp.abs(
            _mixer(0, True, T + c)(jnp.concatenate([junk, h], axis=1))[:, -1]
            - _mixer(0, True, T)(h)[:, -1]).max()) > 1e-3
        order = jnp.concatenate([jax.random.permutation(
            jax.random.PRNGKey(8), T - 1), jnp.asarray([T - 1])])
        for rope, same in ((False, True), (True, False)):
            a = _mixer(0, rope, T)(h)[:, -1]
            b = _mixer(0, rope, T)(h[:, order])[:, -1]
            assert bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-5)) is same


def test_qkv_names_q_and_k_after_the_rotation():
    """What a rematerialised layer keeps under ``QKV_NAME`` is what flash
    takes: with the rotary term on, the kept q and k are the rotated ones."""
    from mxnet_tpu.models import rope as rope_module
    from mxnet_tpu.models.transformer import QKV_NAME
    cfg = TransformerConfig(**TOY)
    model = TransformerLM(cfg)
    bp = block_leaves(bench_weights.init(TOY, 3), 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    q0, k0, v0 = model._qkv(bp, h)
    q1, k1, v1 = model._qkv(bp, h, rope=cfg.rope)
    cos, sin = rope_module.rope_tables(cfg.rope, 32, 24)
    np.testing.assert_allclose(q1, rope_module.rotate_half(q0, cos, sin),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(k1, rope_module.rotate_half(k0, cos, sin),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(v1, v0)
    text = str(jax.make_jaxpr(lambda h: model._qkv(bp, h, rope=cfg.rope))(h))
    named = [ln for ln in text.splitlines() if QKV_NAME in ln]
    assert len(named) == 3
    # the reference's tables are the program's
    ref_cos, ref_sin = ref.rope_tables(TOY, 24)
    np.testing.assert_allclose(ref_cos, cos, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ref_sin, sin, rtol=1e-6, atol=1e-7)


# -- refused by name ----------------------------------------------------------
DENSE = dict(vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
             max_len=64, dtype="float32")
MLA = dict(attention="mla", kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8)


@pytest.mark.parametrize("key", ["attn_windows", "attn_rope"])
def test_a_window_or_a_rotary_term_beside_latent_attention_is_refused(key):
    with pytest.raises(AssertionError, match="beside latent attention"):
        TransformerConfig(**dict(DENSE, **MLA, **{key: [0, 1]}))


@pytest.mark.parametrize("key", ["attn_windows", "attn_rope"])
def test_a_window_or_a_rotary_term_on_a_mamba_layer_is_refused(key):
    types = ("attention", "mamba")
    with pytest.raises(AssertionError, match="names a Mamba layer"):
        TransformerConfig(**dict(DENSE, layer_types=types, **{key: [0, 4]}))
    TransformerConfig(**dict(DENSE, layer_types=types, **{key: [4, 0]}))


def test_an_entry_a_layer_or_none():
    with pytest.raises(AssertionError, match="attn_windows names 3 layers"):
        TransformerConfig(**dict(DENSE, attn_windows=[0, 4, 4]))


def test_ring_attention_over_sp_with_a_window_is_refused():
    model = TransformerLM(TransformerConfig(**dict(DENSE,
                                                   attn_windows=[4, 4])))
    q = jnp.zeros((1, 8, 4, 8))
    with pytest.raises(AssertionError,
                       match="ring attention over sp with a window"):
        model._attend(q, q, q, use_ring=True, window=4)


def test_the_capacity_dispatch_over_ep_with_a_pre_attention_router_is_refused():
    from mxnet_tpu.parallel import make_mesh
    cfg = TransformerConfig(**dict(DENSE, use_moe=True, n_experts=4,
                                   moe_router_pre_attention=True))
    model = TransformerLM(cfg)
    bp = block_leaves(model.init(jax.random.PRNGKey(0)), 0)
    x = jnp.zeros((1, 8, 32))
    with make_mesh(ep=2, devices=jax.devices()[:2]):
        with pytest.raises(AssertionError, match="pre-attention router"):
            model._experts(bp, x, model._route(bp, x))


def test_a_pre_attention_router_without_experts_is_refused():
    with pytest.raises(AssertionError, match="without an expert layer"):
        TransformerConfig(**dict(DENSE, moe_router_pre_attention=True))


@pytest.mark.parametrize("over", [dict(attn_windows=[0, 4]),
                                  dict(attn_rope=[1, 1])])
def test_serving_refuses_per_layer_windows_and_rotary_by_name(over):
    model = TransformerLM(TransformerConfig(**dict(DENSE, **over)))
    with pytest.raises(NotImplementedError, match="R-m4"):
        model.init_kv_pages(4, 8)
