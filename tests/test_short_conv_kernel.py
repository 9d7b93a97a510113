"""``gated_short_conv`` (`ops/pallas/short_conv.py`): the kernels through
the Pallas interpreter against the plain form and ``jax.grad`` of it, at
shapes where the time tiles' halos matter (several tiles, a length off the
sublane tile, the taps up to the halo's 9); what ``kernel_impl`` picks and
counts; the scopes its calls carry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops.pallas import short_conv as sc


def operands(B, T, D, K, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, c, u, g = (jax.random.normal(k, (B, T, D)).astype(dtype)
                  for k in (ks[0], ks[1], ks[2], ks[4]))
    w = jax.random.normal(ks[3], (K, D)).astype(dtype)
    return b, c, u, w, g


def plain(b, c, u, w):
    """The definition, written out: ``y_t = c_t sum_j w[j] b_{t-K+1+j}
    u_{t-K+1+j}``, zero before the start."""
    K, T = w.shape[0], b.shape[1]
    out = []
    for t in range(T):
        z = 0.0
        for j in range(K):
            s = t - K + 1 + j
            if s >= 0:
                z = z + w[j] * b[:, s] * u[:, s]
        out.append(c[:, t] * z)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("B,T,D,K", [
    (2, 40, 64, 3),           # five 8-row tiles: every halo crosses a tile
    (1, 37, 128, 3),          # padded to 40 after the end
    (2, 16, 256, 9),          # taps reaching the whole halo
    (1, 5, 64, 3),            # shorter than a tile
    (1, 1030, 512, 4),        # 129 tiles of 8 rows
])
def test_kernels_match_the_plain_form_and_its_gradient(B, T, D, K):
    b, c, u, w, g = operands(B, T, D, K)
    with jax.default_matmul_precision("highest"):
        y = sc.gated_short_conv(b, c, u, w, interpret=True)
        np.testing.assert_allclose(y, sc.gated_short_conv_lax(b, c, u, w),
                                   rtol=1e-5, atol=1e-5)
        if T <= 40:
            np.testing.assert_allclose(y, plain(b, c, u, w), rtol=1e-5,
                                       atol=1e-5)

        def grads(fn):
            return jax.grad(lambda *a: jnp.sum(fn(*a) * g),
                            argnums=(0, 1, 2, 3))(b, c, u, w)
        got = grads(lambda *a: sc.gated_short_conv(*a, interpret=True))
        want = grads(sc.gated_short_conv_lax)
    for name, x, z in zip("bcuw", got, want):
        scale = float(jnp.abs(z).max())
        np.testing.assert_allclose(x, z, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=name)


def test_bfloat16_in_float32_inside():
    """bfloat16 planes: the result and the gradients in bfloat16, the
    arithmetic in float32 (agrees with the plain form's float32 to the
    last bfloat16 bit)."""
    b, c, u, w, g = operands(2, 64, 128, 3, jnp.bfloat16)
    y = sc.gated_short_conv(b, c, u, w, interpret=True)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        y.astype(jnp.float32), sc.gated_short_conv_lax(b, c, u, w).astype(
            jnp.float32), rtol=1e-2, atol=1e-2)
    got = jax.grad(lambda *a: jnp.sum(sc.gated_short_conv(
        *a, interpret=True).astype(jnp.float32) * g.astype(jnp.float32)),
        argnums=(0, 1, 2, 3))(b, c, u, w)
    assert [x.dtype for x in got] == [jnp.bfloat16] * 4


@pytest.mark.parametrize("T,D,tiles", [
    (8192, 2048, (512, 512)), (1000, 256, (8, 256)), (40, 64, (8, 64)),
    (768, 384, (256, 128))])
def test_tiles_from_the_shape(T, D, tiles):
    assert sc._choose_tiles(T, D) == tiles


def test_the_rule_picks_and_counts(monkeypatch):
    b, c, u, w, _ = operands(1, 16, 64, 3)

    def count(mode):
        monkeypatch.setenv("MXTPU_PALLAS", mode)
        key = "pallas.select.short_conv.%s" % (
            "interpret" if mode == "interpret" else "fallback")
        before = telemetry.registry().counter(key).value
        # a function of its own: a trace of one already traced is cached
        jax.make_jaxpr(lambda *a: sc.gated_short_conv(*a))(b, c, u, w)
        return telemetry.registry().counter(key).value - before

    assert count("interpret") == 1
    assert count("off") == 1


def test_the_calls_carry_their_names():
    """The two kernels' ``pallas_call``s are named as the scopes round them
    (``profiler.DEVICE_SCOPES``), so the device table files them there."""
    b, c, u, w, g = operands(1, 16, 64, 3)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(sc.gated_short_conv(
        *a, interpret=True) * g), argnums=(0, 1, 2, 3)))(b, c, u, w)
    text = str(jaxpr)
    assert "short_conv_fwd" in text and "short_conv_bwd" in text
