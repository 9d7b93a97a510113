"""``TransformerLM`` with a per-layer pattern (``layer_types``): Mamba-1
mixers beside attention, key/value heads shared by query heads, a gated MLP,
a tied head, float32 leaves among the model's dtype — and, with the
defaults, the dense LM's program as it was."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models import TransformerConfig, TransformerLM
from mxnet_tpu.models import mamba
from mxnet_tpu.models.transformer import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = dict(vocab_size=128, d_model=32, n_heads=4, n_layers=4, d_ff=64,
           max_len=64, dtype="float32")
HYBRID = dict(TOY, n_kv_heads=1, mlp="swiglu", tie_embeddings=True,
              layer_types=("mamba", "attention", "attention", "mamba"),
              ssm_state=8, ssm_dt_rank=4)
JAMBA = dict(vocab_size=65536, d_model=2560, n_heads=20, n_kv_heads=1,
             n_layers=14, d_ff=8192, dtype="bfloat16", mlp="swiglu",
             tie_embeddings=True, ssm_dt_rank=160,
             layer_types=("mamba",) * 7 + ("attention",) + ("mamba",) * 6)


def tokens(batch=2, seq=24, vocab=128, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                           vocab)
    return t[:, :-1], t[:, 1:]


def test_runs_of_layers_and_their_index_into_each_stack():
    cfg = TransformerConfig(**HYBRID)
    att, ssm = (("attention", (0, None, 4), "dense"),
                ("mamba", (0, None, 4), "dense"))
    assert cfg.layer_runs() == [
        (ssm, 0, 1, {"mamba": (0, 1), "dense": (0, 1)}),
        (att, 1, 3, {"attention": (0, 2), "dense": (1, 3)}),
        (ssm, 3, 4, {"mamba": (1, 2), "dense": (3, 4)})]
    assert [(key[0], lo, hi, *own[key[0]]) for key, lo, hi, own in
            TransformerConfig(**JAMBA).layer_runs()] == [
        ("mamba", 0, 7, 0, 7), ("attention", 7, 8, 0, 1),
        ("mamba", 8, 14, 7, 13)]
    assert TransformerConfig(**TOY).layer_runs() == [
        (att, 0, TOY["n_layers"], {"attention": (0, TOY["n_layers"]),
                                   "dense": (0, TOY["n_layers"])})]
    # a configuration read from JSON brings a list; dt_rank defaults to
    # ceil(d_model / 16)
    cfg = TransformerConfig(**dict(HYBRID, layer_types=list(
        HYBRID["layer_types"]), ssm_dt_rank=0))
    assert cfg.layer_types == HYBRID["layer_types"]
    assert cfg.ssm_dt_rank == 2 and cfg.d_inner == 64 and cfg.kv_heads == 1


@pytest.mark.parametrize("bad", [
    dict(layer_types=("mamba",)),                 # names 1 layer of 4
    dict(layer_types=("mamba", "lstm", "mamba", "mamba")),
    dict(n_kv_heads=3),                           # 4 heads in groups of 3
    dict(mlp="relu"),
    # beside experts Mamba layers build since PR 38; an expert bias without
    # an expert layer does not
    dict(layer_types=("mamba",) * 4, moe_expert_bias=True),
])
def test_a_configuration_that_cannot_be_built_is_refused(bad):
    with pytest.raises(AssertionError):
        TransformerConfig(**dict(TOY, **bad))


def test_leaves_their_stacks_and_the_float32_ones():
    model = TransformerLM(TransformerConfig(**dict(HYBRID,
                                                   dtype="bfloat16")))
    p = model.init(jax.random.PRNGKey(0))
    assert "unembed" not in p and "blocks.wqkv" not in p
    assert p["blocks.w_gate"].shape == (4, 32, 64)
    assert p["attn.wqkv"].shape == (2, 32, (4 + 2) * 8)
    assert p["attn.wo"].shape == (2, 32, 32)
    assert p["ssm.in_proj"].shape == (2, 32, 128)
    assert p["ssm.conv_w"].shape == (2, 4, 64)
    assert p["ssm.x_proj"].shape == (2, 64, 4 + 2 * 8)
    assert p["ssm.A_log"].shape == (2, 64, 8)
    for name, leaf in p.items():
        want = jnp.float32 if name.split(".")[-1] in mamba.F32_LEAVES \
            else jnp.bfloat16
        assert leaf.dtype == want, name
    # Mamba's start: A = -(1..N) in every channel, steps in [1e-3, 1e-1]
    np.testing.assert_allclose(jnp.exp(p["ssm.A_log"][0, 0]),
                               np.arange(1, 9), rtol=1e-6)
    step = jax.nn.softplus(p["ssm.dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1001


def test_one_period_of_jamba2_3b_is_1_598_556_096_parameters():
    model = TransformerLM(TransformerConfig(**JAMBA))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == 1598556096
    f32 = sum(int(np.prod(s.shape)) for s in shapes.values()
              if s.dtype == jnp.float32)
    assert f32 == 13 * (5120 * 16 + 2 * 5120)


def test_one_key_value_head_is_the_same_head_broadcast_to_every_query():
    """Multi-query attention against the equal-head model whose K and V
    columns repeat the shared head: same logits, and the shared head's
    gradient is the sum over the group."""
    shared = TransformerLM(TransformerConfig(**dict(TOY, n_kv_heads=1)))
    full = TransformerLM(TransformerConfig(**TOY))
    ps = shared.init(jax.random.PRNGKey(0))
    q, k, v = jnp.split(ps["blocks.wqkv"], [32, 40], axis=-1)
    pf = dict(ps)
    pf["blocks.wqkv"] = jnp.concatenate(
        [q, jnp.tile(k, (1, 1, 4)), jnp.tile(v, (1, 1, 4))], axis=-1)
    x, y = tokens()
    np.testing.assert_allclose(shared.apply(ps, x)[0], full.apply(pf, x)[0],
                               rtol=1e-5, atol=1e-5)
    gs = jax.grad(shared.loss)(ps, x, y)["blocks.wqkv"]
    gf = jax.grad(full.loss)(pf, x, y)["blocks.wqkv"]
    gq, gk, gv = jnp.split(gf, [32, 64], axis=-1)
    summed = jnp.concatenate(
        [gq, gk.reshape(4, 32, 4, 8).sum(2), gv.reshape(4, 32, 4, 8).sum(2)],
        axis=-1)
    np.testing.assert_allclose(gs, summed, rtol=1e-4, atol=1e-6)


def test_tied_head_is_the_embedding_matrix_used_twice():
    tied = TransformerLM(TransformerConfig(**dict(TOY, tie_embeddings=True)))
    untied = TransformerLM(TransformerConfig(**TOY))
    pt = tied.init(jax.random.PRNGKey(0))
    pu = dict(pt, unembed=pt["embed"].T)
    x, y = tokens()
    np.testing.assert_allclose(tied.apply(pt, x)[0], untied.apply(pu, x)[0],
                               rtol=1e-5, atol=1e-5)
    gt = jax.grad(tied.loss)(pt, x, y)["embed"]
    gu = jax.grad(untied.loss)(pu, x, y)
    np.testing.assert_allclose(gt, gu["embed"] + gu["unembed"].T, rtol=1e-4,
                               atol=1e-6)


def test_gated_mlp_is_down_of_silu_gate_times_up():
    model = TransformerLM(TransformerConfig(**dict(TOY, n_layers=1,
                                                   mlp="swiglu")))
    p = model.init(jax.random.PRNGKey(0))
    bp = {k.split(".", 1)[1]: v[0] for k, v in p.items()
          if k.startswith("blocks.")}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 32))
    got, _aux = model._mlp_half(bp, x)
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    want = x + (jax.nn.silu(h @ bp["w_gate"]) * (h @ bp["w_up"])
                ) @ bp["w_down"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_mixer_leaves_none_of_its_terms_out():
    """Each term of the published layer moves the output: the three inner
    norms' scales, D u, the gate z, the convolution's bias and taps."""
    cfg = TransformerConfig(**HYBRID)
    p = mamba.mamba_init(cfg, jax.random.PRNGKey(0), 1)
    bp = {k: v[0] for k, v in p.items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 32))
    base = mamba.mamba_mixer(bp, h, cfg)
    for leaf in ("dt_norm_scale", "b_norm_scale", "c_norm_scale", "D",
                 "conv_b", "conv_w", "dt_bias", "A_log", "dt_proj", "x_proj"):
        moved = mamba.mamba_mixer(dict(bp, **{leaf: bp[leaf] * 1.5 + 0.1}),
                                  h, cfg)
        assert float(jnp.max(jnp.abs(moved - base))) > 1e-4, leaf
    # causal: a later input does not move an earlier output
    later = mamba.mamba_mixer(bp, h.at[:, 8:].add(1.0), cfg)
    np.testing.assert_allclose(later[:, :8], base[:, :8], atol=1e-6)
    assert float(jnp.max(jnp.abs(later[:, 8:] - base[:, 8:]))) > 1e-3
    # the convolution is depthwise over each channel's last 4 steps
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 64))
    conv = mamba._causal_conv(u, bp["conv_w"], bp["conv_b"])
    want = bp["conv_b"] + sum(
        bp["conv_w"][k] * (u[0, 5 - (3 - k)] if 5 - (3 - k) >= 0 else 0.0)
        for k in range(4))
    np.testing.assert_allclose(conv[0, 5], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(conv[0, 0], bp["conv_b"]
                               + bp["conv_w"][3] * u[0, 0], rtol=1e-5,
                               atol=1e-6)


def test_hybrid_trains_and_the_kernels_through_the_interpreter_agree(
        monkeypatch):
    """Three steps of ``make_train_step`` with the lax paths, then the same
    with every Pallas kernel (scan, flash, rmsnorm, xent) through the
    interpreter: same losses, same parameters.  The dense gate is at 0, so
    that attention is flash at this toy size (dense would pass this test
    without a kernel): flash forward and backward inside the model, under
    remat and the layer scan, with the shared key/value head broadcast."""
    from mxnet_tpu import telemetry
    model = TransformerLM(TransformerConfig(
        **dict(HYBRID, dense_attn_max_score_mb=0)))
    x, y = tokens(seq=32)
    kernels = ("selective_scan", "flash_attention", "fused_rmsnorm",
               "fused_softmax_xent")

    def selected(impl):
        counters = telemetry.registry().snapshot()["counters"]
        return [int(counters.get("pallas.select.%s.%s" % (k, impl), 0))
                for k in kernels]

    def three_steps():
        p = model.init(jax.random.PRNGKey(0))
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        step = jax.jit(make_train_step(model, lr=0.05))
        losses = []
        for _ in range(3):
            p, v, loss = step(p, v, x, y)
            losses.append(float(loss))
        return losses, p

    before = selected("fallback")
    losses, p = three_steps()
    assert losses[2] < losses[0]
    assert all(b > a for a, b in zip(before, selected("fallback")))
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    before = selected("interpret")
    losses_k, p_k = three_steps()
    # every kernel was asked for, and through the interpreter
    assert all(b > a for a, b in zip(before, selected("interpret")))
    np.testing.assert_allclose(losses_k, losses, rtol=2e-5)
    for name in p:
        np.testing.assert_allclose(p_k[name], p[name], rtol=1e-3, atol=2e-5,
                                   err_msg=name)


def test_remat_and_rolled_scans_give_the_same_loss():
    x, y = tokens()
    losses = []
    for over in (dict(), dict(remat=False), dict(scan_unroll=False)):
        model = TransformerLM(TransformerConfig(**dict(HYBRID, **over)))
        p = model.init(jax.random.PRNGKey(0))
        losses.append(float(jax.jit(model.loss)(p, x, y)))
    np.testing.assert_allclose(losses[1:], losses[0], rtol=1e-6)


@pytest.mark.parametrize("over,names", [
    (dict(HYBRID), "R-m5"),
    (dict(TOY, n_kv_heads=1), "R-m2"),
    (dict(TOY, mlp="swiglu"), "R-m2"),
    (dict(TOY, tie_embeddings=True), "R-m2"),
])
def test_paged_decode_refuses_what_it_cannot_serve(over, names):
    model = TransformerLM(TransformerConfig(**over))
    p = model.init(jax.random.PRNGKey(0))
    x, _y = tokens(batch=1, seq=8)
    pages = jnp.zeros((4, 4, 8, 4, 8))
    with pytest.raises(NotImplementedError, match=names):
        model.prefill(p, pages, pages, x, 8, jnp.zeros((2,), jnp.int32))
    with pytest.raises(NotImplementedError, match=names):
        model.decode_step(p, pages, pages, x[0, :1],
                          jnp.zeros((1, 2), jnp.int32),
                          jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
    if over.get("layer_types") or over.get("n_kv_heads"):
        with pytest.raises(NotImplementedError, match=names):
            model.init_kv_pages(4, 8)


def test_the_dense_lm_traces_the_program_it_traced_before_layer_types(
        monkeypatch):
    """``pythia-1.4b-sizes`` (its rehearsal sizes) through
    ``jax.jit(make_train_step)`` lowers to the text it lowered to at the
    commit before ``layer_types``, ``n_kv_heads``, ``mlp`` and
    ``tie_embeddings`` existed (sha256 taken on that commit with this JAX),
    but for one thing: since PR 30 the layer norms' lax form is
    ``layers._rmsnorm_lax`` (``xf * xf``) and no longer the model's own copy
    (``jnp.square(xf)``, whose derivative is written ``2 x dx``).  With the
    copy put back the text is the old one, byte for byte.
    A PR that means to change the dense LM's program records the new
    digest here and says so.  PR 31: the rematerialised layer keeps the
    mixer's output (at these sizes attention is dense, so flash names
    nothing); with nothing kept the text was the one before, byte for
    byte (``6be2beb4...``).  PR 33: ``_qkv`` names q, k and v heads first
    (a transposition, the name, the transposition back: XLA cancels the
    pair against flash's own) and the policy keeps them; the layer loop's
    ``unroll`` is the run's length where it was ``True`` (2 layers are
    under the bound: the same text).  With nothing kept the names and
    their transpositions still stand in the text, so that digest moved
    too; that the values are PR 31's is held by
    ``test_lm_remat_kept.py`` (every gradient to the bit)."""
    from mxnet_tpu.models import transformer
    monkeypatch.setenv("MXTPU_PALLAS", "off")
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "pythia-1.4b-sizes.json")) as f:
        m = json.load(f)["rehearsal"]["model"]
    model = TransformerLM(TransformerConfig(**m))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(shapes) == {
        "embed", "blocks.ln1_scale", "blocks.ln2_scale", "blocks.wqkv",
        "blocks.wo", "blocks.w_up", "blocks.w_down", "final_ln_scale",
        "unembed"}
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def digest():
        text = jax.jit(make_train_step(model, lr=0.01, momentum=0.9),
                       donate_argnums=(0, 1)).lower(shapes, shapes, tok,
                                                    tok).as_text()
        return hashlib.sha256(text.encode()).hexdigest()

    assert digest() == (
        "6d3d6a61eb9daa07cda7a85dd60b1494c2d8ff4389e16010d62b5049ec180287")
    monkeypatch.setattr(transformer, "KEPT", ())
    assert digest() == (
        "5e4ceb107bd67b49d31fbbaa234de3ed343f03772c34ed9b6872b61fd23c82c7")


def test_the_models_name_no_implementation_and_contract_each_weight_once():
    """Which kernel runs is the kernel's entry point's business, and the
    block is written once: one function a weight."""
    import inspect
    from mxnet_tpu.models import transformer
    for module in (transformer, mamba):
        src = inspect.getsource(module)
        for word in ('"pallas"', '"interpret"', '"fallback"', '"sharded"',
                     "select_impl", "kernel_impl", "logsumexp", "rsqrt"):
            assert word not in src, (module.__name__, word)
    src = inspect.getsource(transformer)
    for leaf in ("wqkv", "wo", "w_up", "w_down", "w_gate"):
        assert src.count('bp["%s"]' % leaf) == 1, leaf
    for gone in ("with_kv", "_ssm_block", "def attn_half", "def mlp_half"):
        assert gone not in src, gone


@pytest.mark.parametrize("pad_pages", [0, 3])
def test_prefill_and_decode_run_the_one_block_and_agree_with_apply(
        monkeypatch, pad_pages):
    """``prefill`` and ``decode_step`` trace ``_block`` once each (one scan
    body for both layers) and give the full forward's logits position by
    position, with a page table padded past the pages in use and beside an
    inactive slot."""
    model = TransformerLM(TransformerConfig(**dict(TOY, n_layers=2)))
    p = model.init(jax.random.PRNGKey(0))
    block, calls = model._block, []

    def counted(*a, **kw):
        calls.append(1)
        return block(*a, **kw)

    monkeypatch.setattr(model, "_block", counted)
    ps, n_prompt, n = 4, 6, 11
    seq = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, 128)
    full = model.apply(p, seq[None])[0][0]
    calls.clear()
    table = jnp.asarray([1, 2, 3] + [0] * pad_pages, jnp.int32)
    kp, vp = model.init_kv_pages(5, ps)
    prompt = jnp.zeros((1, 8), jnp.int32).at[0, :n_prompt].set(seq[:n_prompt])
    kp, vp, logits = model.prefill(p, kp, vp, prompt, n_prompt, table)
    assert calls == [1]
    np.testing.assert_allclose(logits, full[n_prompt - 1], rtol=1e-5,
                               atol=1e-5)
    step = jax.jit(model.decode_step)
    tables = jnp.stack([table, jnp.zeros_like(table)])
    for t in range(n_prompt, n):
        kp, vp, logits = step(
            p, kp, vp, jnp.stack([seq[t], seq[0]]), tables,
            jnp.asarray([t, 0], jnp.int32), jnp.asarray([True, False]))
        np.testing.assert_allclose(logits[0], full[t], rtol=1e-5, atol=1e-5)
    assert calls == [1, 1]
