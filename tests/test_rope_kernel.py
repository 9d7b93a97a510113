"""The rotary kernels (`ops/pallas/rope.py`) through the Pallas interpreter
against ``rotate_half`` and ``jax.vjp`` of it: the forward and the gradient
agree to a bfloat16 ulp at the cells' head counts scaled down (q and k
heads 9 : 1, 6 : 1, 7 : 1; a 64-wide head two to a row), the whole head
turning and a partial YaRN part, a length off the position block; the lane
tables against ``rope_tables``; what ``select`` picks and counts, and that
the fallback is ``rotate_half``'s; a toy ``TransformerLM`` with a window
layer, a partial-YaRN full layer and q/k norms gives the fallback's loss
and gradients through the kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerConfig, TransformerLM
from mxnet_tpu.models.rope import (RopeSetting, rope_lane_tables,
                                   rope_tables, rotate_half)
from mxnet_tpu.ops.pallas import rope as pr

PLAIN = RopeSetting()
YARN = RopeSetting(theta=500000, factor=128, orig_len=8192, fraction=0.5)


def lax_heads(x, groups, D, setting):
    """The lax form the model keeps: each group's heads, turned by
    ``rotate_half`` where the group turns, transposed heads first."""
    B, T, _ = x.shape
    tables = rope_tables(setting, setting.dims(D), T)
    outs, at = [], 0
    for n, turns in groups:
        h = x[..., at * D:(at + n) * D].reshape(B, T, n, D)
        at += n
        outs.append((rotate_half(h, *tables) if turns else h
                     ).transpose(0, 2, 1, 3))
    return tuple(outs)


def within_an_ulp(got, want, dtype):
    """bfloat16: |got - want| at most one ulp of ``want``, or, where the
    sum of the two float32 terms cancels, their own float32 rounding (the
    two forms may fuse a product into the add or not): 1e-6 of the
    largest.  float32: to 1e-6 of the value and of the largest."""
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    atol = 1e-6 * np.abs(want).max()
    if dtype != jnp.bfloat16:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=atol)
        return
    ulp = np.exp2(np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    bad = np.abs(got - want) > np.maximum(ulp, atol)
    assert not bad.any(), (bad.sum(), np.abs(got - want).max())


# (q, k heads; head width; setting; T; dtype)
CASES = [
    # 1,100 rows in blocks of 1,024: a partial last block
    pytest.param(9, 1, 128, PLAIN, 1100, jnp.bfloat16,
                 id="laguna_window_72_8_partial_last_block"),
    pytest.param(6, 1, 128, YARN, 24, jnp.bfloat16,
                 id="laguna_full_48_8_yarn_64of128"),
    pytest.param(7, 1, 128, PLAIN, 48, jnp.float32,
                 id="smallthinker_28_4_f32"),
    pytest.param(8, 2, 64, PLAIN, 40, jnp.bfloat16,
                 id="lfm2_32_8_d64_two_heads_a_row"),
    pytest.param(4, 2, 64, RopeSetting(fraction=0.5), 33, jnp.bfloat16,
                 id="d64_partial_32of64_odd_length"),
]


@pytest.mark.parametrize("H,KV,D,setting,T,dtype", CASES)
@pytest.mark.parametrize("groups", ["qkv", "q_only", "k_only"])
def test_kernels_are_rotate_half_and_its_gradient(H, KV, D, setting, T,
                                                  dtype, groups):
    groups = {"qkv": ((H, True), (KV, True), (KV, False)),
              "q_only": ((H, True),), "k_only": ((KV, True),)}[groups]
    N = sum(n for n, _ in groups)
    ks = jax.random.split(jax.random.PRNGKey(T + N), 1 + len(groups))
    x = jax.random.normal(ks[0], (2, T, N * D)).astype(dtype)
    cos, sin = rope_lane_tables(setting, D, T)
    rot = setting.dims(D)

    def kernel(x):
        return pr.rope_heads(x, cos, sin, groups, D, rot, interpret=True)

    got, vjp = jax.vjp(jax.jit(kernel), x)
    want, vjp_want = jax.vjp(
        jax.jit(lambda x: lax_heads(x, groups, D, setting)), x)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == dtype
        within_an_ulp(a, b, dtype)
    gs = tuple(jax.random.normal(k, w.shape).astype(dtype)
               for k, w in zip(ks[1:], want))
    (dx,), (dx_want,) = vjp(gs), vjp_want(gs)
    assert dx.dtype == dtype
    within_an_ulp(dx, dx_want, dtype)


def test_the_blocks_from_the_shape():
    """Eight head rows a step where every group holds a multiple of 8
    (Laguna), 512 rows of them in bfloat16; a partial block past 1,024."""
    assert pr._plan(((72, True), (8, True), (8, False)), 128, 8192, 2) == (
        ((0, 9, True), (9, 1, True), (10, 1, False)), 8, 512, 11)
    assert pr._plan(((28, True), (4, True), (4, False)), 128, 16384, 2)[1:] \
        == (4, 1024, 9)
    assert pr._plan(((32, True),), 64, 8192, 2)[1:] == (8, 512, 2)
    assert pr._plan(((9, True), (1, True), (1, False)), 128, 1100, 2)[1:] \
        == (1, 1024, 11)


@pytest.mark.parametrize("setting,D", [(PLAIN, 128), (YARN, 128),
                                       (PLAIN, 64)])
def test_lane_tables_are_rope_tables_laid_out_a_head_at_a_time(setting, D):
    T = 20
    cos, sin = rope_lane_tables(setting, D, T)
    rot = setting.dims(D)
    c, s = rope_tables(setting, rot, T)
    assert cos.shape == sin.shape == (T, 128)
    for h in range(128 // D):
        head = slice(h * D, (h + 1) * D)
        np.testing.assert_array_equal(cos[:, head][:, :rot], c)
        np.testing.assert_array_equal(sin[:, head][:, :rot // 2],
                                      -s[:, :rot // 2])
        np.testing.assert_array_equal(sin[:, head][:, rot // 2:rot],
                                      s[:, rot // 2:])
        np.testing.assert_array_equal(cos[:, head][:, rot:], 1.0)
        np.testing.assert_array_equal(sin[:, head][:, rot:], 0.0)


@pytest.mark.parametrize("D,rot,counts,takes", [
    (128, 128, (72, 8), True), (128, 64, (48, 8), True),
    (64, 64, (32, 8), True),
    (64, 64, (3, 1), False),        # a row would hold half a group
    (128, 96, (4, 1), False),       # a rotary part that does not divide
    (192, 64, (16, 16), False)])    # a head wider than a row
def test_the_shape_rule(D, rot, counts, takes):
    assert pr.takes(D, rot, counts) is takes


def counter(key):
    return telemetry.registry().counter(key).value


@pytest.mark.parametrize("mode,D,impl", [
    ("interpret", 128, "interpret"), ("off", 128, "fallback"),
    ("auto", 128, "fallback"),       # the CPU
    ("interpret", 96, "fallback")])  # refused by the shape rule
def test_select_picks_and_counts(monkeypatch, mode, D, impl):
    monkeypatch.setenv("MXTPU_PALLAS", mode)
    key = "pallas.select.rope." + impl
    before = counter(key)
    assert pr.select(D, D, (4, 2)) is {"interpret": True,
                                       "fallback": None}[impl]
    assert counter(key) == before + 1


def test_each_call_counts_its_pass_and_carries_its_name():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 5 * 128))
    cos, sin = rope_lane_tables(YARN, 128, 16)
    keys = ["pallas.rope.%s.4x128.64of128" % p for p in ("fwd", "bwd")]
    before = [counter(k) for k in keys]
    text = str(jax.make_jaxpr(jax.grad(lambda x: sum(
        jnp.sum(y) for y in pr.rope_heads(
            x, cos, sin, ((3, True), (1, True), (1, False)), 128, 64,
            interpret=True))))(x))
    assert [counter(k) - b for k, b in zip(keys, before)] == [1, 1]
    assert "rope_fwd" in text and "rope_bwd" in text


def toy(D, qk_norm):
    """Two layers: a full one with a partial YaRN part, a window one
    turning whole heads; 4 query heads on 2 key/value heads, float32."""
    return TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, head_dim=D,
        n_layers=2, d_ff=96, max_len=64, dtype="float32",
        attn_windows=(0, 8), qk_norm=qk_norm,
        attn_rope=(dict(theta=500000, factor=4, orig_len=16,
                        fraction=0.5), 1))


@pytest.mark.parametrize("D,qk_norm", [(64, True), (128, False)])
def test_the_model_through_the_kernels_is_the_fallback(monkeypatch, D,
                                                       qk_norm):
    """Loss and every gradient leaf of a toy model, the rotary term through
    the kernels (each layer's q and k) against ``rotate_half``, at the
    tolerances of the model's reference tests."""
    cfg = toy(D, qk_norm)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    t = jax.random.randint(jax.random.PRNGKey(4), (2, 25), 0, 128)

    def run(mode):
        monkeypatch.setenv("MXTPU_PALLAS", mode)
        model = TransformerLM(cfg)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(model.loss))(
                params, t[:, :-1], t[:, 1:])

    before = counter("pallas.select.rope.interpret")
    got, g_got = run("interpret")
    # a body a layer: the full and the window layer each asked once
    assert counter("pallas.select.rope.interpret") == before + 2
    want, g_want = run("off")
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert set(g_got) == set(g_want)
    for name in sorted(g_want):
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_the_fallback_is_rotate_half(monkeypatch):
    """Where the rule answers ``fallback`` the step holds no rotary kernel
    and turns q and k as ``rotate_half`` does."""
    monkeypatch.setenv("MXTPU_PALLAS", "off")
    cfg = toy(128, False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(3))
    t = jax.random.randint(jax.random.PRNGKey(4), (2, 17), 0, 128)
    before = counter("pallas.select.rope.fallback")
    text = str(jax.make_jaxpr(model.loss)(params, t[:, :-1], t[:, 1:]))
    assert "rope_fwd" not in text
    assert counter("pallas.select.rope.fallback") == before + 2
    bp = {k.split(".", 1)[1]: v[0] for k, v in params.items()
          if k.startswith("blocks.")}
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 64))
    with jax.default_matmul_precision("highest"):
        q, k, _v = model._qkv(bp, h, cfg.attn_rope[0])
        qkv = h @ bp["wqkv"]
    tables = rope_tables(cfg.attn_rope[0], 64, 16)
    np.testing.assert_allclose(
        q, rotate_half(qkv[..., :512].reshape(1, 16, 4, 128), *tables),
        rtol=1e-5, atol=1e-5)
