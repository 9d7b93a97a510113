"""``TransformerLM`` with latent attention (``attention="mla"``) and routed
experts by layer (``mlp_types``): the MLA mixer with its decoupled rotary
part, flash attention at a value width of its own, the grouped product, the
expert layer that holds a share of the experts and drops no token -- each
against the benchmark's plain reference (``benchmarks/harness/
ref_mla_moe_lm.py``, which imports nothing of the program) or against lax."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.models import TransformerConfig, TransformerLM, mla, rope
from mxnet_tpu.models.transformer import default_rules, make_train_step
from mxnet_tpu.ops.pallas import flash_attention, grouped_matmul
from mxnet_tpu.parallel.moe import expert_layer, route

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
from harness import ref_mla_moe_lm as ref  # noqa: E402
from harness import weights_mla_moe_lm as bench_weights  # noqa: E402

with open(os.path.join(REPO, "benchmarks", "configs",
                       "deepseek-v2-lite-l5-e32.json")) as _f:
    CONFIG = json.load(_f)
FULL = CONFIG["model"]
# the configuration's rehearsal sizes: 4 layers of which 1 dense, 8 experts
# of which 4 held, top-2, float32
TOY = dict(FULL, **CONFIG["rehearsal"]["model"])


def tokens(batch=2, seq=24, vocab=512, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              vocab)


# -- YaRN -------------------------------------------------------------------
def test_yarn_check_values_of_the_published_rope_scaling():
    cfg = TransformerConfig(**FULL)
    assert rope.yarn_correction_range(64, 10000, 4096, 32, 1) == (10, 23)
    m = rope.yarn_mscale(40, 0.707)
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.58963,
                                                   rel=1e-5)
    inv = rope.yarn_inv_freq(64, 10000, 40, 4096, 32, 1)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # published frequencies up to pair 10, a fortieth from pair 23 on, and a
    # linear blend between
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-12)
    assert inv[16] == pytest.approx(
        base[16] * (1 - 6 / 13) + base[16] / 40 * (6 / 13), rel=1e-12)
    # the factor on cos and sin is mscale / mscale_all_dim = 1
    cos, sin = rope.rope_tables(cfg.rope, cfg.qk_rope_head_dim, 8)
    np.testing.assert_allclose(cos[0], 1.0)
    np.testing.assert_allclose(sin[1, :32], np.sin(inv), rtol=1e-5)
    np.testing.assert_allclose(ref.yarn_inv_freq(FULL), inv, rtol=1e-12)
    assert ref.softmax_scale(FULL) == pytest.approx(mla.softmax_scale(cfg))


def test_no_scaling_is_plain_rope_and_a_plain_scale():
    cfg = TransformerConfig(**dict(TOY, rope_factor=1.0,
                                   rope_mscale_all_dim=0.0))
    assert mla.softmax_scale(cfg) == pytest.approx(24 ** -0.5)
    np.testing.assert_allclose(
        rope.yarn_inv_freq(8, 10000, 1.0, 32, 32, 1),
        10000.0 ** (-np.arange(0, 8, 2) / 8))


def test_rotate_half_turns_each_pair_by_its_angle_and_keeps_the_norm():
    cfg = TransformerConfig(**TOY)
    cos, sin = rope.rope_tables(cfg.rope, cfg.qk_rope_head_dim, 5)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    y = rope.rotate_half(x, cos, sin)
    np.testing.assert_allclose(y[:, 0], x[:, 0], rtol=1e-6)  # position 0
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(y[0, :, 0], ref.rope(x[0], cos, sin)[:, 0],
                               rtol=1e-6, atol=1e-6)


# -- the model against the plain reference ----------------------------------
def _layer_leaves(params, i, kind, at):
    lp = {k: params["blocks." + k][i] for k in ref.COMMON}
    lp.update({k: params[ref.PREFIX[kind] + k][at] for k in ref.OWN[kind]})
    return lp


def test_mla_mixer_is_the_references():
    model = TransformerLM(TransformerConfig(**TOY))
    p = bench_weights.init(TOY, 5)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    bp = {k.split(".", 1)[1]: v[1] for k, v in p.items()
          if k.startswith("blocks.")}
    with jax.default_matmul_precision("highest"):
        got = model._mla(bp, h)[0]
        want = ref.mla_mixer(_layer_leaves(p, 1, "moe", 0), h, TOY,
                             lambda a: a)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("mode", ["lax", "kernels"])
def test_loss_and_every_gradient_leaf_are_the_references(mode, monkeypatch):
    """The whole toy model (1 dense layer, 3 expert layers holding 4 of 8
    experts, top-2) against the reference's loss and gradients.  ``kernels``
    runs flash at 24/16, the grouped product, rmsnorm and the cross-entropy
    through the Pallas interpreter (the dense gate at 0)."""
    over = {}
    if mode == "kernels":
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
        over = dict(dense_attn_max_score_mb=0)
    model = TransformerLM(TransformerConfig(**dict(TOY, **over)))
    p = bench_weights.init(TOY, 11)
    assert set(p) == set(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    t = tokens()
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(model.loss))(
            p, t[:, :-1], t[:, 1:])
        want, g_want = jax.jit(jax.value_and_grad(
            lambda q: ref.forward_loss(TOY, q, t)))(p)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for name in sorted(g_want):
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_three_train_steps_descend_and_count_the_kernels(monkeypatch):
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    model = TransformerLM(TransformerConfig(
        **dict(TOY, dense_attn_max_score_mb=0)))
    p = bench_weights.init(TOY, 2)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    t = tokens(seq=32)
    step = jax.jit(make_train_step(model, lr=0.05))
    before = dict(telemetry.registry().snapshot()["counters"])
    losses = []
    for _ in range(3):
        p, v, loss = step(p, v, t[:, :-1], t[:, 1:])
        losses.append(float(loss))
    assert losses[2] < losses[0]
    after = telemetry.registry().snapshot()["counters"]

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)

    assert grew("pallas.select.grouped_matmul.interpret") >= 3
    assert grew("pallas.select.flash_attention.interpret") >= 1
    assert grew("moe.experts_held.4of8") >= 1
    assert grew("moe.buffer_rows.%d" % (2 * 32 * 2)) >= 1
    assert grew("moe.combine.rows_kept.%dx%d" % (2 * 32 * 2,
                                                 TOY["d_model"])) >= 1
    assert any(k.startswith("pallas.gmm.tile.gmm_dw.") and grew(k)
               for k in after)


def test_the_cut_is_1_732_534_784_parameters_at_the_published_widths():
    model = TransformerLM(TransformerConfig(**FULL))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == 1732534784
    assert bench_weights.param_count(FULL) == 1732534784
    assert shapes["blocks.wq"].shape == (5, 2048, 16 * 192)
    assert shapes["blocks.wkv_a"].shape == (5, 2048, 512 + 64)
    assert shapes["blocks.wkv_b"].shape == (5, 512, 16 * 256)
    assert shapes["blocks.wo"].shape == (5, 2048, 2048)
    assert shapes["dense.w_up"].shape == (1, 2048, 10944)
    assert shapes["moe.gate"].shape == (4, 2048, 64)      # all 64 outputs
    assert shapes["moe.moe_up"].shape == (4, 32, 2048, 1408)
    assert shapes["moe.shared_down"].shape == (4, 2816, 2048)
    assert {k: v[0] for k, v in bench_weights.leaf_shapes(FULL).items()} \
        == {k: v.shape for k, v in shapes.items()}
    # the file keeps every published number; the two cut keys are named
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert CONFIG["published"]["n_routed_experts"] == FULL["n_experts"] == 64
    assert CONFIG["n_routed_experts"] == len(FULL["experts_held"]) == 32
    assert CONFIG["num_experts_per_tok"] == FULL["moe_top_k"] == 6


@pytest.mark.parametrize("bad", [
    dict(mlp_types=("dense",)),                      # names 1 layer of 4
    dict(mlp_types=("dense", "moe", "relu", "moe")),
    dict(attention="gqa"),
    dict(moe_top_k=9),                               # of 8 experts
    dict(experts_held=(0, 8)),
    dict(layer_types=("mamba",) * 4),                # beside experts, MLA
])
def test_a_configuration_that_cannot_be_built_is_refused(bad):
    with pytest.raises(AssertionError):
        TransformerConfig(**dict(TOY, **bad))


@pytest.mark.parametrize("over,names", [
    (dict(TOY), "R-m3"),
    (dict(TOY, mlp_types=()), "R-m2"),               # MLA alone
    (dict(vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
          use_moe=True, n_experts=4), "R-m3"),
])
def test_serving_names_what_it_still_refuses(over, names):
    from mxnet_tpu.generation import GenerationEngine
    model = TransformerLM(TransformerConfig(**over))
    with pytest.raises(NotImplementedError, match=names):
        model._refuse_serving()
    with pytest.raises(NotImplementedError, match=names):
        GenerationEngine(model, {})


def test_default_rules_have_specs_for_the_new_leaves():
    from jax.sharding import PartitionSpec as P
    rules = default_rules()
    assert rules.spec_for("blocks.wq") == P(None, "fsdp", "tp")
    assert rules.spec_for("blocks.wqkv") == P(None, "fsdp", "tp")
    assert rules.spec_for("blocks.wkv_a") == P(None, "fsdp", None)
    assert rules.spec_for("blocks.wkv_b") == P(None, None, "tp")
    assert rules.spec_for("blocks.wo") == P(None, "tp", "fsdp")
    assert rules.spec_for("dense.w_down") == P(None, "tp", "fsdp")
    assert rules.spec_for("moe.moe_gate") == P(None, "ep", "fsdp", None)
    assert rules.spec_for("moe.moe_down") == P(None, "ep", None, "fsdp")
    assert rules.spec_for("moe.shared_gate") == P(None, "fsdp", "tp")
    assert rules.spec_for("moe.shared_down") == P(None, "tp", "fsdp")
    assert rules.spec_for("moe.gate") == P(None, "fsdp", None)
    assert rules.spec_for("blocks.kv_norm_scale") == P()


# -- flash attention at a value width of its own ----------------------------
def _dense_attention(q, k, v, scale):
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("T,D,Dv,block", [
    (40, 24, 16, None),          # one tile, values narrower
    (200, 24, 16, None),         # padded to 256
    (256, 16, 32, 128),          # 2 x 2 tiles, values wider
    (96, 192, 128, None),        # the published widths
])
def test_flash_at_a_value_width_of_its_own_is_dense_attention(T, D, Dv,
                                                              block):
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q = jax.random.normal(ks[0], (2, T, 2, D))
    k = jax.random.normal(ks[1], (2, T, 2, D))
    v = jax.random.normal(ks[2], (2, T, 2, Dv))
    do = jax.random.normal(ks[3], (2, T, 2, Dv))
    scale = 0.37 / np.sqrt(D)

    def flash(q, k, v):
        return flash_attention(q, k, v, scale=scale, block_q=block,
                               block_k=block, interpret=True)

    with jax.default_matmul_precision("highest"):
        o = flash(q, k, v)
        want = _dense_attention(q, k, v, scale)
        g = jax.grad(lambda *a: (flash(*a) * do).sum(), (0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda *a: (_dense_attention(*a, scale) * do).sum(),
                          (0, 1, 2))(q, k, v)
    assert o.shape == (2, T, 2, Dv)
    np.testing.assert_allclose(o, want, rtol=2e-5, atol=2e-5)
    for a, b in zip(g, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_tiles_of_an_equal_width_call_are_what_they_were():
    from mxnet_tpu.ops.pallas.flash_attention import (_choose_tiles,
                                                      _working_set)
    for kernel in ("fwd", "dq", "dkv"):
        assert _working_set(kernel, 1024, 1024, 128, 2) == _working_set(
            kernel, 1024, 1024, 128, 2, 128)
    assert _choose_tiles(2048, 2048, 128, 2) == ((1024, 1024),) * 3
    assert _choose_tiles(4096, 4096, 192, 2, 128) == ((1024, 1024),) * 3
    # narrower values need less room than q's width for all four operands
    assert _working_set("fwd", 1024, 1024, 256, 2, 128) < _working_set(
        "fwd", 1024, 1024, 256, 2)


# -- the grouped product ----------------------------------------------------
_GMM_SHAPES = [
    (40, 16, 24, [5, 0, 11, 7], 8),          # an empty group, a tail
    (37, 16, 24, [5, 0, 11, 7, 14], 8),      # rows off the tile, no tail
    (64, 256, 384, [0, 0, 64, 0], 16),       # one group takes all
    (64, 16, 8, [0, 0, 0, 0], 16),           # nothing live
    (24, 16, 8, [1, 23], 16),
    (96, 32, 16, [30, 31, 32], 512),         # one tile of 96 rows
]


@pytest.mark.parametrize("M,K,N,sizes,tm,rows_past_groups", [
    pytest.param(*shape, rows, id="%d-%d-%d-sizes%d-%d%s" % (
        *shape[:3], i, shape[4], "" if rows == "zero" else "-unread"))
    for rows in ("zero", "unread") for i, shape in enumerate(_GMM_SHAPES)])
def test_grouped_product_is_ragged_dot_forward_dx_and_dw(
        M, K, N, sizes, tm, rows_past_groups, monkeypatch):
    """``"zero"``: every row, those past the groups zero; ``"unread"``: the
    groups' rows of the forward and of ``dx`` (the interpreter leaves a
    tile no step writes NaN), and ``dW`` whole."""
    module = sys.modules["mxnet_tpu.ops.pallas.grouped_matmul"]
    monkeypatch.setattr(module, "_TM", tm)
    ks = jax.random.split(jax.random.PRNGKey(M + N), 3)
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (len(sizes), K, N))
    dy = jax.random.normal(ks[2], (M, N))
    gs = jnp.asarray(sizes, jnp.int32)

    def kernel(x, w):
        return grouped_matmul(x, w, gs, interpret=True,
                              rows_past_groups=rows_past_groups)

    def plain(x, w):
        return lax.ragged_dot(x, w, gs)

    with jax.default_matmul_precision("highest"):
        o, want = kernel(x, w), plain(x, w)
        g = jax.grad(lambda *a: (kernel(*a) * dy).sum(), (0, 1))(x, w)
        g_want = jax.grad(lambda *a: (plain(*a) * dy).sum(), (0, 1))(x, w)
    live = sum(sizes)
    rows = M if rows_past_groups == "zero" else live
    np.testing.assert_allclose(o[:rows], want[:rows], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(g[0][:rows], g_want[0][:rows], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(g[1], g_want[1], rtol=1e-5, atol=1e-4)
    if rows_past_groups == "zero":
        assert not np.asarray(o[live:]).any()    # rows past the groups
    else:
        # the tile that holds the last group's end is written, zeros past
        # it; the tiles after it are not
        tile = min(tm, -(-M // 8) * 8)
        end = min(-(-live // tile) * tile, M)
        for got in (o, g[0]):
            assert not np.asarray(got[live:end]).any()
            assert np.isnan(np.asarray(got[end:])).all()
    for i, n in enumerate(sizes):                # an empty group's dW
        if n == 0:
            assert not np.asarray(g[1][i]).any()


@pytest.mark.parametrize("sizes", [[5, 0, 11, 7], [0, 0, 30, 10],
                                   [10, 10, 10, 10]])
def test_the_lax_form_is_ragged_dot_on_the_cpu(sizes):
    """``grouped_matmul_lax`` (group by group under a mask: what the CPU and
    a mesh get) against ``lax.ragged_dot``, which is right on the CPU; on
    the TPU its instruction is not (PERF.md, PR 32)."""
    from mxnet_tpu.ops.pallas import grouped_matmul_lax
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (40, 16))
    w = jax.random.normal(ks[1], (4, 16, 24))
    dy = jax.random.normal(ks[2], (40, 24))
    gs = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: (grouped_matmul_lax(
            *a, gs) * dy).sum(), (0, 1))(x, w)
        want = jax.value_and_grad(lambda *a: (lax.ragged_dot(
            *a, gs) * dy).sum(), (0, 1))(x, w)
        out = grouped_matmul_lax(x, w, gs)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    np.testing.assert_allclose(got[1][0], want[1][0], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[1][1], want[1][1], rtol=1e-5, atol=1e-4)
    assert not np.asarray(out[sum(sizes):]).any()
    assert not np.asarray(got[1][0][sum(sizes):]).any()


def test_grouped_product_splits_a_contraction_that_does_not_fit(monkeypatch):
    """With the budget cut the tiles step down and the contraction runs
    over several grid steps into the accumulator."""
    module = sys.modules["mxnet_tpu.ops.pallas.grouped_matmul"]
    monkeypatch.setattr(module, "_TM", 16)
    monkeypatch.setattr(module, "_VMEM_BUDGET", 60_000)
    assert module._choose_tiles("gmm_fwd", 48, 512, 256, 4) == (16, 128, 128)
    assert module._choose_tiles("gmm_dw", 48, 512, 256, 4) == (16, 128, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (48, 512))
    w = jax.random.normal(ks[1], (3, 512, 256))
    dy = jax.random.normal(ks[2], (48, 256))
    gs = jnp.asarray([20, 9, 12], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: (grouped_matmul(
            *a, gs, interpret=True) * dy).sum(), (0, 1))(x, w)
        want = jax.value_and_grad(lambda *a: (lax.ragged_dot(
            *a, gs) * dy).sum(), (0, 1))(x, w)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    np.testing.assert_allclose(got[1][0], want[1][0], rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got[1][1], want[1][1], rtol=1e-4, atol=1e-3)


def test_schedule_visits_each_tile_once_more_a_boundary_and_skips_the_rest():
    module = sys.modules["mxnet_tpu.ops.pallas.grouped_matmul"]
    sizes = jnp.asarray([5, 0, 11, 7], jnp.int32)
    offsets, group_of, tile_of, n = module._schedule(
        sizes, 40, 8, tail=True, visit_empty=False)
    assert offsets.tolist() == [0, 5, 5, 16, 23, 40]
    n = int(n[0])
    # group 0: tile 0; group 2: tiles 0, 1; group 3: tile 2; the tail (4):
    # tiles 2, 3, 4; the steps past those repeat the last
    assert list(zip(group_of.tolist()[:n], tile_of.tolist()[:n])) == [
        (0, 0), (2, 0), (2, 1), (3, 2), (4, 2), (4, 3), (4, 4)]
    assert set(zip(group_of.tolist()[n:], tile_of.tolist()[n:])) <= {(4, 4)}
    # the forward's and dx's with the rows past the groups unread: no tail,
    # tiles 3 and 4 never visited, the steps past the live ones repeat the
    # last group's last tile
    offsets, group_of, tile_of, n = module._schedule(
        sizes, 40, 8, tail=False, visit_empty=False)
    assert offsets.tolist() == [0, 5, 5, 16, 23]
    assert len(group_of) == 40 // 8 + 4 - 1
    n = int(n[0])
    assert list(zip(group_of.tolist()[:n], tile_of.tolist()[:n])) == [
        (0, 0), (2, 0), (2, 1), (3, 2)]
    assert set(zip(group_of.tolist()[n:], tile_of.tolist()[n:])) == {(3, 2)}
    _, group_of, tile_of, n = module._schedule(
        sizes, 40, 8, tail=False, visit_empty=True)
    n = int(n[0])
    assert list(zip(group_of.tolist()[:n], tile_of.tolist()[:n])) == [
        (0, 0), (1, 0), (2, 0), (2, 1), (3, 2)]


# -- the expert layer -------------------------------------------------------
def _experts(seed=0, n=8, held=8, E=16, F=24, gated=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        router=jax.random.normal(ks[0], (E, n)),
        up=jax.random.normal(ks[1], (held, E, F)) / 4,
        down=jax.random.normal(ks[2], (held, F, E)) / 5,
        gate=jax.random.normal(ks[3], (held, E, F)) / 4 if gated else None)


def _dense_oracle(x, w, top_k, held, renormalize=False, relu_gate=False):
    """Every token through its chosen experts, one at a time, in numpy."""
    toks = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = toks @ np.asarray(w["router"], np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    out = np.zeros_like(toks)
    for t in range(toks.shape[0]):
        top = np.argsort(-s[t], kind="stable")[:top_k]
        g = s[t][top] / (s[t][top].sum() if renormalize else 1.0)
        for gi, e in zip(g, top):
            if e not in held:
                continue
            i = list(held).index(e)
            up = toks[t] @ np.asarray(w["up"][i], np.float64)
            if w["gate"] is not None:
                a = toks[t] @ np.asarray(w["gate"][i], np.float64)
                up = (np.maximum(a, 0) if relu_gate
                      else a / (1 + np.exp(-a))) * up
            else:
                up = np.maximum(up, 0)
            out[t] += gi * (up @ np.asarray(w["down"][i], np.float64))
    return out.reshape(x.shape)


@pytest.mark.parametrize("top_k,gated,interpret,act", [
    (2, True, False, None), (3, True, True, None),
    (1, False, True, jax.nn.relu), (2, True, False, jax.nn.relu)],
    ids=["2-swiglu", "3-swiglu-interpret", "1-relu-interpret", "2-reglu"])
def test_expert_layer_is_each_token_through_its_chosen_experts(
        top_k, gated, interpret, act, monkeypatch):
    """``act`` is the activation of ``up(h)`` in the first form and of
    ``gate(h)`` in the gated one (None: ``silu`` there)."""
    if interpret:
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    w = _experts(gated=gated)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 12, 16))
    with jax.default_matmul_precision("highest"):
        y, aux, held = expert_layer(x, w["router"], w["up"], w["down"],
                                    w["gate"], top_k=top_k,
                                    renormalize=False, act=act)
    np.testing.assert_allclose(
        y, _dense_oracle(x, w, top_k, range(8), relu_gate=act is not None),
        rtol=1e-4, atol=1e-5)
    assert float(held) == 2 * 12 * top_k
    # balanced routing would read 1; any routing reads a positive number
    assert float(aux) > 0


@pytest.mark.parametrize("interpret", [False, True])
def test_every_token_sent_to_one_expert_loses_none(interpret, monkeypatch):
    """The old capacity dispatch kept ``capacity`` tokens an expert and
    dropped the rest; here all 48 tokens of both slots go through expert 5
    and expert 2, whatever the imbalance."""
    if interpret:
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    w = _experts(seed=3)
    router = jnp.zeros((16, 8)).at[:, 5].set(0.5).at[:, 2].set(0.25)
    w["router"] = router
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 48, 16))) + 0.1
    with jax.default_matmul_precision("highest"):
        y, _aux, held = expert_layer(x, router, w["up"], w["down"],
                                     w["gate"], top_k=2, renormalize=False)
        _w, chosen, _a = route(x.reshape(48, 16), router, 2)
    assert set(np.asarray(chosen).ravel().tolist()) == {2, 5}
    want = _dense_oracle(x, w, 2, range(8))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert float(held) == 96
    assert float(np.abs(want).min(axis=-1).max()) > 0   # no token got zero
    assert (np.abs(np.asarray(y)).sum(-1) > 0).all()


def test_the_two_shares_add_up_to_the_uncut_reference_layer():
    """DeepSeek-V2-Lite's expert layer at toy widths, 8 experts, top-3: the
    share holding experts 0-3 and the share holding 4-7 each give their own
    experts' terms plus the shared expert; with the shared expert counted
    once they add up to the reference layer that holds all 8."""
    m = dict(TOY, n_experts=8, moe_top_k=3, experts_held=list(range(8)))
    p = bench_weights.init(dict(m), 21)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    lp = {k: p["moe." + k][1] for k in ref.OWN["moe"]}
    with jax.default_matmul_precision("highest"):
        whole, aux_whole, on_held = ref.expert_mlp(lp, h, m, lambda a: a)
        shared = ref.gated_mlp(h, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"], lambda a: a)
        parts, held_pairs = [], 0.0
        for share in ((0, 1, 2, 3), (4, 5, 6, 7)):
            cfg = TransformerConfig(**dict(m, experts_held=share))
            bp = dict(lp, **{k: lp[k][jnp.asarray(share)] for k in
                             ("moe_gate", "moe_up", "moe_down")})
            ff, aux = TransformerLM(cfg)._experts(bp, h)
            # every share computes the balance term over all 8 alike
            assert float(aux[0]) == pytest.approx(float(aux_whole), rel=1e-5)
            held_pairs += float(aux[1])
            parts.append(ff)
            # and the reference given the same share is that share
            half, _a, _n = ref.expert_mlp(bp, h, dict(m, experts_held=share),
                                          lambda a: a)
            np.testing.assert_allclose(ff, half, rtol=1e-4, atol=1e-5)
    assert float(on_held) == held_pairs == 2 * 16 * 3
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole,
                               rtol=1e-4, atol=1e-5)


def test_held_slot_share_counts_the_pairs_that_landed_on_held_experts():
    model = TransformerLM(TransformerConfig(**TOY))
    p = bench_weights.init(TOY, 4)
    t = tokens()
    share = float(jax.jit(model.held_slot_share)(p, t[:, :-1]))
    tr = ref.TrainReference(TOY, p, {"lr": 0.01, "momentum": 0.9})
    tr.step(np.asarray(t))
    assert share == pytest.approx(tr.held_shares[0], abs=1e-6)
    assert 0.2 < share < 0.8
    everything = TransformerLM(TransformerConfig(
        **dict(TOY, experts_held=tuple(range(8)))))
    p_all = everything.init(jax.random.PRNGKey(0))
    assert float(everything.held_slot_share(p_all, t[:, :-1])) == 1.0


def test_use_moe_goes_through_the_layer_without_a_capacity():
    """``use_moe`` (experts in every layer, all held, top-1, the MLP's own
    form) is the same layer: a batch whose tokens all choose one expert
    keeps every token's term."""
    cfg = TransformerConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=1,
                            d_ff=24, dtype="float32", use_moe=True,
                            n_experts=4)
    model = TransformerLM(cfg)
    p = model.init(jax.random.PRNGKey(0))
    assert p["blocks.moe_up"].shape == (1, 4, 16, 24)
    bp = {k.split(".", 1)[1]: v[0] for k, v in p.items()
          if k.startswith("blocks.")}
    bp["gate"] = jnp.zeros((16, 4)).at[:, 3].set(1.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 10, 16))) + 0.1
    with jax.default_matmul_precision("highest"):
        ff, aux = model._experts(bp, h)
        up = jax.nn.gelu(h @ bp["moe_up"][3])
        s = jax.nn.softmax(h.reshape(10, 16) @ bp["gate"], -1)[:, 3]
        want = (up @ bp["moe_down"][3]) * s[None, :, None]
    np.testing.assert_allclose(ff, want, rtol=1e-4, atol=1e-6)
    assert float(aux[1]) == 10
