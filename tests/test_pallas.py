"""Pallas kernel parity tests: kernels (interpret mode) vs lax fallbacks.

Mirrors the reference's accelerator-vs-CPU `check_consistency` strategy
(`/root/reference/python/mxnet/test_utils.py:1224`): the lax fallback is the
oracle; the Pallas kernels run through the interpreter on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops.pallas import (flash_attention, flash_attention_lse,
                                  fused_rmsnorm, fused_softmax_xent,
                                  int8_matmul, int8_matmul_lax, kernel_impl,
                                  kernel_unit, selective_scan,
                                  selective_scan_lax)
from mxnet_tpu.ops.pallas.flash_attention import _flash  # noqa: F401
from mxnet_tpu.ops.pallas.flash_attention import (_VMEM_LIMIT, _choose_tiles,
                                                   _padded, _working_set)
from mxnet_tpu.ops.pallas.int8_matmul import _int8_matmul_pallas
from mxnet_tpu.ops.pallas.layers import _rmsnorm_lax, _xent_lax
from mxnet_tpu.parallel.ring_attention import blockwise_attention


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("shape", [(2, 128, 4, 64), (1, 256, 2, 32)])
    def test_forward_parity(self, causal, shape):
        B, T, H, D = shape
        q = _rand(0, shape)
        k = _rand(1, shape)
        v = _rand(2, shape)
        ref = blockwise_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_unaligned_seq_padding(self):
        # T=100 is not a multiple of the kernel block; pad path must mask
        q = _rand(0, (1, 100, 2, 32))
        k = _rand(1, (1, 100, 2, 32))
        v = _rand(2, (1, 100, 2, 32))
        for causal in (True, False):
            ref = blockwise_attention(q, k, v, causal=causal)
            out = flash_attention(q, k, v, causal=causal, interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_lse_parity(self, causal):
        # flash_attention_lse (the ring-attention block kernel) must agree
        # with the lax blockwise oracle on BOTH the normalized output and
        # the logsumexp, or merged partials drift
        shape = (2, 100, 2, 32)          # unaligned T exercises padding
        q = _rand(0, shape)
        k = _rand(1, shape)
        v = _rand(2, shape)
        ref_o, ref_lse = blockwise_attention(q, k, v, causal=causal,
                                             return_lse=True)
        out_o, out_lse = flash_attention_lse(q, k, v, causal=causal,
                                             interpret=True)
        np.testing.assert_allclose(np.asarray(out_o), np.asarray(ref_o),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_lse), np.asarray(ref_lse),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_parity(self):
        shape = (1, 128, 2, 32)
        q = _rand(0, shape)
        k = _rand(1, shape)
        v = _rand(2, shape)

        def loss_ref(q, k, v):
            return (blockwise_attention(q, k, v, causal=True) ** 2).sum()

        def loss_ker(q, k, v):
            return (flash_attention(q, k, v, causal=True,
                                    interpret=True) ** 2).sum()

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gk = jax.grad(loss_ker, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg="d%s mismatch" % name)

    def test_bf16_inputs(self):
        shape = (1, 128, 2, 32)
        q = _rand(0, shape, jnp.bfloat16)
        k = _rand(1, shape, jnp.bfloat16)
        v = _rand(2, shape, jnp.bfloat16)
        ref = blockwise_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_cpu_fallback_dispatch(self):
        # with interpret unset on CPU, must silently use the lax fallback
        q = _rand(0, (1, 64, 2, 16))
        out = flash_attention(q, q, q, causal=True)
        ref = blockwise_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


class TestFlashAttentionLSEGrad:
    """flash_attention_lse carries a custom VJP over BOTH outputs: the lse
    cotangent folds into the backward kernels' delta operand.  The loss
    below depends on o AND lse, so a wrong fold-in fails loudly."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("T", [128, 100])   # 100: ragged last block
    def test_grad_parity_vs_blockwise_oracle(self, causal, T):
        shape = (1, T, 2, 32)
        q = _rand(0, shape)
        k = _rand(1, shape)
        v = _rand(2, shape)

        def loss_ref(q, k, v):
            o, lse = blockwise_attention(q, k, v, causal=causal,
                                         return_lse=True)
            return (o ** 2).sum() + jnp.tanh(lse).sum()

        def loss_ker(q, k, v):
            o, lse = flash_attention_lse(q, k, v, causal=causal,
                                         interpret=True)
            return (o ** 2).sum() + jnp.tanh(lse).sum()

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gk = jax.grad(loss_ker, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg="d%s mismatch" % name)

    def test_output_only_loss_matches_flash_attention_grad(self):
        # with no lse cotangent the VJP must reduce to the plain one
        shape = (1, 128, 2, 32)
        q, k, v = _rand(0, shape), _rand(1, shape), _rand(2, shape)
        g1 = jax.grad(lambda q: (flash_attention(
            q, k, v, causal=True, interpret=True) ** 2).sum())(q)
        g2 = jax.grad(lambda q: (flash_attention_lse(
            q, k, v, causal=True, interpret=True)[0] ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                                   rtol=1e-5, atol=1e-5)


def _dense_attention(q, k, v, causal):
    """Dense float32 oracle that takes Tq != Tk (`blockwise_attention` takes
    one length), with the kernels' top-left-aligned causal mask.  Returns
    ``(o, lse)`` like ``flash_attention_lse``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    if causal:
        keep = (jnp.arange(q.shape[1])[:, None]
                >= jnp.arange(k.shape[1])[None, :])
        s = jnp.where(keep, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return o, lse


class TestFlashBlockSchedule:
    """Explicit small tiles, so that several tiles, skipped ones and ragged
    last ones exist at CPU sizes: forward, lse and gradient against the
    oracles; the tile chooser as a pure function; the telemetry."""

    # (Tq, Tk, block_q, block_k): block_q != block_k both ways; a ragged T
    # (100 in 32-tiles: the last tile holds 28 padded keys); Tq != Tk
    CASES = [(128, 128, 32, 16), (128, 128, 16, 32), (100, 100, 32, 32),
             (64, 128, 32, 32), (128, 64, 16, 32)]

    @staticmethod
    def _both(Tq, Tk, bq, bk, causal, dtype=jnp.float32):
        q = _rand(0, (2, Tq, 2, 32), dtype)
        k = _rand(1, (2, Tk, 2, 32), dtype)
        v = _rand(2, (2, Tk, 2, 32), dtype)
        w = _rand(3, (2, Tq, 2, 32))

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v)
                return ((o.astype(jnp.float32) * w).sum()
                        + jnp.tanh(lse).sum()), (o, lse)
            return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

        (_, got), got_g = loss(lambda q, k, v: flash_attention_lse(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            interpret=True))(q, k, v)
        (_, want), want_g = loss(lambda q, k, v: _dense_attention(
            q, k, v, causal))(*(x.astype(jnp.float32) for x in (q, k, v)))
        return got, got_g, want, want_g

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("Tq,Tk,bq,bk", CASES)
    def test_parity_small_tiles(self, Tq, Tk, bq, bk, causal):
        got, got_g, want, want_g = self._both(Tq, Tk, bq, bk, causal)
        for a, b, name in zip(got, want, ("o", "lse")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5, err_msg=name)
        for a, b, name in zip(got_g, want_g, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg="d%s mismatch" % name)

    @pytest.mark.parametrize("T", [128, 100, 64])
    def test_dense_oracle_agrees_with_blockwise(self, T):
        # the oracle above against the repo's own, where both apply
        q, k, v = (_rand(i, (2, T, 2, 32)) for i in range(3))
        o, lse = _dense_attention(q, k, v, True)
        ref_o, ref_lse = blockwise_attention(q, k, v, causal=True,
                                             return_lse=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("Tq,Tk,bq,bk", [CASES[0], CASES[2], CASES[3]])
    def test_bf16_small_tiles(self, Tq, Tk, bq, bk, causal):
        # bfloat16 operands reach the MXU as bfloat16 (p and ds are rounded
        # too): within bfloat16's resolution of the float32 oracle's largest
        got, got_g, want, want_g = self._both(Tq, Tk, bq, bk, causal,
                                              jnp.bfloat16)
        assert got[0].dtype == jnp.bfloat16
        assert all(g.dtype == jnp.bfloat16 for g in got_g)
        for a, b in zip(list(got) + list(got_g), list(want) + list(want_g)):
            b = np.asarray(b, np.float32)
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       rtol=0, atol=2e-2 * np.abs(b).max())

    def test_float32_operands_stay_float32(self):
        # the cast follows the input: no bfloat16 in a float32 call's kernels
        q = _rand(0, (1, 64, 1, 32))
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, q, q, block_q=32, block_k=32, interpret=True).sum()))(q)
        assert "bf16" not in str(jaxpr)

    @pytest.mark.parametrize("itemsize", [2, 4])
    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("T", [8, 100, 128, 1000, 1024, 1100, 2048,
                                   8192])
    def test_choose_tiles(self, T, D, itemsize):
        from mxnet_tpu.ops.pallas.common import _round_up
        padded = _padded(T)
        assert padded == (_round_up(T, 8) if T <= 128
                          else _round_up(T, 128))
        tiles = _choose_tiles(T, T, D, itemsize)
        assert len(tiles) == 3
        for kernel, (bq, bk) in zip(("fwd", "dq", "dkv"), tiles):
            assert padded % bq == 0 and padded % bk == 0, (kernel, bq, bk)
            assert bq % 8 == 0 and bk % 8 == 0
            assert _working_set(kernel, bq, bk, D, itemsize) <= _VMEM_LIMIT
        if T == 2048:
            # long sequences leave the 128-tile: far fewer grid steps
            assert all(bq >= 256 and bk >= 256 for bq, bk in tiles)

    def test_choose_tiles_sides_apart(self):
        # each side from its own length: a sub-tile query block against a
        # long key sequence, and the other way round
        for bq, bk in _choose_tiles(100, 2048, 128, 2):
            assert bq == 104 and 2048 % bk == 0 and bk >= 256
        for bq, bk in _choose_tiles(1100, 64, 128, 2):
            assert bq == 128 and bk == 64

    def test_choose_tiles_steps_down_to_fit(self):
        # float32 at D = 256: the top rung's working set is over the ask for
        # the kernels that hold three score-sized tiles, not for the forward
        fwd, dq, dkv = _choose_tiles(2048, 2048, 256, 4)
        assert fwd == (1024, 1024)
        assert _working_set("dkv", 1024, 1024, 256, 4) > _VMEM_LIMIT
        assert dkv != (1024, 1024)
        for kernel, (bq, bk) in zip(("fwd", "dq", "dkv"), (fwd, dq, dkv)):
            assert 2048 % bq == 0 and 2048 % bk == 0
            assert _working_set(kernel, bq, bk, 256, 4) <= _VMEM_LIMIT

    def test_tile_counter_and_run_share_gauge(self):
        from mxnet_tpu import telemetry
        reg = telemetry.registry()
        names = ["pallas.flash.tile.%s.32x32" % k
                 for k in ("fwd", "dq", "dkv")]
        before = [reg.counter(n).value for n in names]
        q = _rand(0, (1, 128, 1, 32))
        gauge = reg.gauge("pallas.flash.causal_tiles_run_share")
        # 4 x 4 tiles, causal: 10 of 16 visited, forward and backward alike
        jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, block_q=32, block_k=32,
            interpret=True).sum())(q)
        assert gauge.value == 10 / 16
        assert [reg.counter(n).value for n in names] == \
            [b + 1 for b in before]
        flash_attention(q, q, q, causal=False, block_q=32, block_k=32,
                        interpret=True)
        assert gauge.value == 1.0


class TestInt8Matmul:
    def _data(self, M, K, N, seed=0):
        rng = np.random.RandomState(seed)
        a = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
        w = jnp.asarray(rng.randint(-127, 128, (N, K)), jnp.int8)
        return a, w

    @pytest.mark.parametrize("shape", [(37, 96, 50), (128, 128, 128),
                                       (256, 64, 200)])
    def test_int32_exact_vs_lax(self, shape):
        """No scales: int8 x int8 -> int32 accumulate must be bit-exact
        (zero padding is exact in int32), aligned or ragged."""
        M, K, N = shape
        a, w = self._data(M, K, N)
        out = _int8_matmul_pallas(a, w, interpret=True)
        ref = int8_matmul_lax(a, w)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_fused_dequant_per_channel_oracle(self):
        """scale_a scalar + per-channel scale_b [N] on ragged shapes: the
        in-register dequant must match dequantize-then-dot."""
        M, K, N = 37, 96, 50
        a, w = self._data(M, K, N, seed=1)
        rng = np.random.RandomState(2)
        sa = jnp.float32(0.043)
        sw = jnp.asarray(rng.rand(N).astype(np.float32) * 0.1 + 0.01)
        out = _int8_matmul_pallas(a, w, sa, sw, interpret=True)
        oracle = (np.asarray(a, np.float32) * 0.043) @ \
            (np.asarray(w, np.float32) * np.asarray(sw)[:, None]).T
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), oracle,
                                   rtol=1e-5, atol=1e-4)

    def test_public_api_interpret_override(self):
        # interpret=True on the public entry forces the Pallas kernel
        # even where auto mode would pick the fallback (this CPU run)
        a, w = self._data(32, 64, 40)
        out = int8_matmul(a, w, interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(int8_matmul_lax(a, w)))


KERNELS = ("flash_attention", "fused_rmsnorm", "fused_softmax_xent",
           "selective_scan", "int8_matmul")


def _entry_and_lax(name):
    """``(entry point, its lax form, operands)`` of kernel ``name``."""
    if name == "flash_attention":
        args = tuple(_rand(i, (1, 16, 2, 8)) for i in range(3))
        return flash_attention, blockwise_attention, args
    if name == "fused_rmsnorm":
        args = (_rand(0, (4, 128)), 1.0 + 0.1 * _rand(1, (128,)))
        return fused_rmsnorm, lambda x, s: _rmsnorm_lax(x, s, 1e-6), args
    if name == "fused_softmax_xent":
        labels = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 100)
        return fused_softmax_xent, _xent_lax, (_rand(0, (8, 100)), labels)
    if name == "selective_scan":
        wide, narrow = (1, 16, 128), (1, 16, 8)
        args = (_rand(0, wide), jax.nn.softplus(_rand(1, wide)),
                -jnp.exp(_rand(2, (128, 8))), _rand(3, narrow),
                _rand(4, narrow), _rand(5, (128,)), _rand(6, wide))
        return selective_scan, selective_scan_lax, args
    a = jnp.asarray(np.arange(-32, 32).reshape(8, 8) % 100, jnp.int8)
    return int8_matmul, int8_matmul_lax, (a, a)


def _selections(name):
    from mxnet_tpu import telemetry
    counters = telemetry.registry().snapshot()["counters"]
    return {impl: counters.get("pallas.select.%s.%s" % (name, impl), 0)
            for impl in ("pallas", "sharded", "interpret", "fallback")}


class TestKernelImpl:
    @pytest.mark.parametrize("mode,impl", [("auto", "fallback"),
                                           ("off", "fallback"),
                                           ("interpret", "interpret")])
    @pytest.mark.parametrize("name", KERNELS)
    def test_entry_point_called_directly_follows_the_rule(
            self, monkeypatch, name, mode, impl):
        """On this CPU: one selection counted, under the rule's answer, and
        the result equal to the lax form's."""
        monkeypatch.setenv("MXTPU_PALLAS", mode)
        entry, lax_form, args = _entry_and_lax(name)
        before = _selections(name)
        out = entry(*args)
        moved = {k: v - before[k] for k, v in _selections(name).items()
                 if v != before[k]}
        assert moved == {impl: 1}
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(lax_form(*args)),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("mode", ["auto", "interpret"])
    @pytest.mark.parametrize("name", KERNELS)
    def test_on_a_tpu_under_a_mesh_no_kernel_is_left_to_gspmd(
            self, monkeypatch, name, mode):
        """The backend said to be a TPU, four virtual devices as a mesh: the
        rule offers the kernel only inside flash's ``shard_map`` wrapper,
        and the entry point called directly does as the rule says."""
        import importlib
        from jax.experimental import pallas as pl
        from mxnet_tpu.parallel import make_mesh
        fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
        monkeypatch.setenv("MXTPU_PALLAS", mode)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        flash = name == "flash_attention"
        want = "sharded" if flash and mode == "auto" else "fallback"

        def no_kernel(*a, **kw):
            raise AssertionError("a pallas_call under a mesh")

        monkeypatch.setattr(pl, "pallas_call", no_kernel)
        monkeypatch.setattr(fa, "_over_mesh", lambda q, *a: q)
        entry, lax_form, args = _entry_and_lax(name)
        with make_mesh(devices=jax.devices()[:4], dp=2, tp=2):
            assert kernel_impl(name, sharded=flash) == want
            # a shard_map body is its own device's business
            assert kernel_impl(name, per_device=True) == (
                "pallas" if mode == "auto" else "interpret")
            before = _selections(name)
            out = entry(*args)
            assert _selections(name)[want] == before[want] + 1
        if want == "sharded":
            assert out is args[0]
        else:
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(lax_form(*args)),
                                       rtol=1e-5, atol=1e-5)

    def test_invalid_mode_raises(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PALLAS", "sideways")
        with pytest.raises(ValueError, match="MXTPU_PALLAS"):
            kernel_impl("int8_matmul")

    def test_a_forced_kernel_counts_no_selection(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PALLAS", "off")
        a = jnp.asarray(np.arange(-32, 32).reshape(8, 8) % 100, jnp.int8)
        before = _selections("int8_matmul")
        int8_matmul(a, a, interpret=True)
        assert _selections("int8_matmul") == before

    def test_kernel_unit_memoized_and_labeled(self):
        from mxnet_tpu.dispatch import TrackedJit
        fn = kernel_unit("test_unit_xyz", lambda x: x + 1)
        assert isinstance(fn, TrackedJit)
        assert kernel_unit("test_unit_xyz") is fn
        assert int(fn(jnp.int32(1))) == 2


class TestFusedRMSNorm:
    @pytest.mark.parametrize("shape", [(8, 256), (2, 17, 128), (100, 64)])
    def test_forward_parity(self, shape):
        x = _rand(0, shape)
        scale = 1.0 + 0.1 * _rand(1, shape[-1:])
        ref = _rmsnorm_lax(x, scale, 1e-6)
        out = fused_rmsnorm(x, scale, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_parity(self):
        x = _rand(0, (16, 128))
        scale = 1.0 + 0.1 * _rand(1, (128,))

        gr = jax.grad(lambda x, s: (_rmsnorm_lax(x, s, 1e-6) ** 2).sum(),
                      argnums=(0, 1))(x, scale)
        gk = jax.grad(
            lambda x, s: (fused_rmsnorm(x, s, interpret=True) ** 2).sum(),
            argnums=(0, 1))(x, scale)
        np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        x = _rand(0, (8, 128), jnp.bfloat16)
        scale = jnp.ones((128,), jnp.bfloat16)
        out = fused_rmsnorm(x, scale, interpret=True)
        assert out.dtype == jnp.bfloat16


class TestFusedSoftmaxXent:
    @pytest.mark.parametrize("shape,V", [((32,), 1000), ((4, 16), 128),
                                         ((10,), 77)])
    def test_forward_parity(self, shape, V):
        logits = _rand(0, shape + (V,))
        labels = jax.random.randint(jax.random.PRNGKey(9), shape, 0, V)
        ref = _xent_lax(logits, labels)
        out = fused_softmax_xent(logits, labels, interpret=True)
        assert out.shape == shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_parity(self):
        logits = _rand(0, (16, 256))
        labels = jax.random.randint(jax.random.PRNGKey(9), (16,), 0, 256)

        gr = jax.grad(lambda l: _xent_lax(l, labels).mean())(logits)
        gk = jax.grad(
            lambda l: fused_softmax_xent(l, labels, interpret=True).mean()
        )(logits)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)

    def test_big_vocab_streaming(self):
        # V > block_v forces the streaming path over vocab chunks
        logits = _rand(0, (8, 5000))
        labels = jax.random.randint(jax.random.PRNGKey(9), (8,), 0, 5000)
        ref = _xent_lax(logits, labels)
        out = fused_softmax_xent(logits, labels, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
