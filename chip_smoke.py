"""chip_smoke.py — the quickest proof that this repo still starts on the chip.

One Python process (a chip belongs to one process at a time) drives the
repo's main paths once, through the entry points a user imports, at the full
width of the configurations ``bench.py`` measures, with seeded random weights
and synthetic data, and checks what comes out by the repo's own means:

* leg A — the Gluon trainer: model-zoo ResNet-50 → ``hybridize`` → bf16 cast →
  ``gluon.Trainer`` (sgd, momentum, fp32 master weights) →
  ``gluon.contrib.FusedTrainStep`` at batch 128 × 3 × 224 × 224;
* leg B — the LM trainer and every Pallas kernel, compiled: two
  ``TransformerLM`` configurations under ``jax.jit(make_train_step(model))``
  that between them reach all kernels, then each kernel against its lax
  reference on the same device, forward and gradient;
* leg C — ``GenerationServer`` over the wide LM configuration: paged KV,
  bucketed prefill/decode, eight streamed requests, plus a float32 greedy run
  compared token for token with a no-cache full forward;
* leg D — the wide LM train step on a dp=2 × tp=2 mesh, when the host has
  four chips.

Run it on the chip through the chip tool: ``python chip_smoke.py``.  It sets
no ``JAX_PLATFORMS``, no ``XLA_FLAGS`` and no compile-cache directory.  It
exits non-zero, printing no result, when JAX finds no TPU or when any check
in any leg fails.  On success it prints a ``[chip_smoke] record:`` line — one
JSON object with the versions, the compile-cache directory and, per leg,
``compile_s`` / ``run_s`` (set-up facts for the next builder, not metrics) and
the counters that were checked — and then, as the last line of stdout, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it.

Every leg is a function of its sizes, so ``tests/test_chip_smoke.py`` calls
them at toy size on the CPU; ``__main__`` always runs full width and always
requires the chip.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
import time

# bench.py's transformer leg: 332.4M parameters, dense attention by the
# dense_attn_max_score_mb gate, fused_rmsnorm and fused_softmax_xent
LM_WIDE = dict(vocab_size=32000, d_model=2048, n_heads=16, n_layers=4,
               d_ff=8192, max_len=1024, dtype="bfloat16", remat=False)
# bench.py's long-context leg: head_dim 64 at seq 8192 takes the Pallas flash
# forward and its dQ / dKdV backward kernels
LM_LONG = dict(vocab_size=32000, d_model=1024, n_heads=16, n_layers=4,
               d_ff=4096, max_len=8192, dtype="bfloat16", remat=True)

# the hybrid state-space LM's layer pattern (one attention layer in fourteen,
# at index 7) at a quarter of the benchmark cell's widths: the selective-scan
# kernels forward and backward over 4,096 steps, one multi-query attention
# layer through flash (1 x 16 x 4096^2 scores are over the dense gate), a
# gated MLP, a tied head
LM_HYBRID = dict(vocab_size=16384, d_model=1024, n_heads=16, n_kv_heads=1,
                 n_layers=14, d_ff=2048, max_len=4096, dtype="bfloat16",
                 remat=True, mlp="swiglu", tie_embeddings=True,
                 layer_types=("mamba",) * 7 + ("attention",)
                 + ("mamba",) * 6)

KERNELS = ("flash_attention", "fused_rmsnorm", "fused_softmax_xent",
           "selective_scan")
IMPLS = ("pallas", "sharded", "interpret", "fallback")


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _say(msg):
    print("[chip_smoke] " + msg, flush=True)


def _select_counts():
    """``{kernel: {impl: count}}`` from the ``pallas.select.*`` counters."""
    from mxnet_tpu import telemetry

    counters = telemetry.registry().snapshot()["counters"]
    return {k: {i: int(counters.get("pallas.select.%s.%s" % (k, i), 0))
                for i in IMPLS} for k in KERNELS}


def _select_delta(before):
    after = _select_counts()
    return {k: {i: after[k][i] - before[k][i] for i in IMPLS}
            for k in KERNELS}


def _on_platform(arr, platform, what):
    found = sorted({d.platform for d in arr.devices()})
    _check(found == [platform],
           "%s lives on %s, expected %s" % (what, found, platform))


# ---------------------------------------------------------------------------
# preflight
# ---------------------------------------------------------------------------
def preflight():
    """The device, versions and compile-cache directory in force.  Exits
    non-zero, naming the platform found, when JAX has no TPU."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit("chip_smoke: this run needs a TPU, and JAX found platform "
                 "%r (%s, %d device(s))"
                 % (d0.platform, d0.device_kind, len(devices)))
    from importlib import metadata

    import jaxlib

    import mxnet_tpu as mx

    info = {
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices)},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")},
        "compile_cache_dir": mx.runtime.compile_cache_dir(),
        "peaks": mx.runtime.device_peaks(d0),
    }
    _say("preflight: %s" % json.dumps(info))
    return info


# ---------------------------------------------------------------------------
# leg A — Gluon trainer
# ---------------------------------------------------------------------------
def leg_trainer(ctx, model_name="resnet50_v1", batch=128, size=224,
                classes=1000, warmup=3, steps=20):
    """Fused Gluon train step on a fixed batch, as ``bench.py`` builds it."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import dispatch, gluon, profiler
    from mxnet_tpu.gluon.contrib import FusedTrainStep
    from mxnet_tpu.gluon.model_zoo import vision

    platform = ctx.jax_device().platform
    t0 = time.perf_counter()
    np.random.seed(0)                  # the initializers draw from these
    mx.random.seed(0)
    net = vision.get_model(model_name, classes=classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    rng = np.random.RandomState(0)
    x32 = mx.nd.array(rng.rand(batch, 3, size, size).astype(np.float32),
                      ctx=ctx)
    y = mx.nd.array(rng.randint(0, classes, (batch,)), ctx=ctx)
    with mx.autograd.pause():
        net(x32)                       # finish deferred init in fp32
    net.cast("bfloat16")
    x = x32.astype("bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9,
                             "multi_precision": True})
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)

    def mean_loss(arr):
        return float(np.asarray(arr.asnumpy(), np.float32).mean())

    for _ in range(warmup):
        loss = step(x, y)
    warm_loss = mean_loss(loss)
    compile_s = time.perf_counter() - t0

    base = profiler.dispatch_stats()
    t1 = time.perf_counter()
    out = [step(x, y) for _ in range(steps)]
    losses = [mean_loss(o) for o in out]
    run_s = time.perf_counter() - t1
    stats = profiler.dispatch_stats()

    for name, p in net.collect_params().items():
        _on_platform(p.data().data, platform, "parameter %s" % name)
    _on_platform(out[-1].data, platform, "the loss")
    _check(all(np.isfinite(v) for v in [warm_loss] + losses),
           "non-finite loss: warm-up %r then %r" % (warm_loss, losses))
    _check(losses[-1] < warm_loss,
           "loss did not fall on the fixed batch: %r after warm-up, then %r"
           % (warm_loss, losses))
    recompiles = stats["recompile"] - base["recompile"]
    donated = stats["donated_bytes"] - base["donated_bytes"]
    _check(recompiles == 0,
           "%d recompile(s) after warm-up: %s"
           % (recompiles, dispatch.explain_recompiles()))
    _check(donated > 0, "donated_bytes did not grow: donation is off")
    return {"model": model_name, "batch": batch, "size": size,
            "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
            "steps": steps, "loss_after_warmup": round(warm_loss, 4),
            "loss_final": round(losses[-1], 4),
            "recompiles_after_warmup": recompiles,
            "donated_bytes": int(donated)}


# ---------------------------------------------------------------------------
# leg B — LM trainer and the kernels
# ---------------------------------------------------------------------------
def leg_lm_train(cfg_kw, batch, seq, steps=3, expect_impl="pallas", lr=1e-2,
                 flash=True):
    """``jax.jit(make_train_step(model))`` on a fixed batch; checks the loss
    and which implementation ``kernel_impl`` gave each kernel the model
    called (the selective scan only where it has state-space layers; not
    ``flash`` where the caller says the model's own gate keeps attention
    dense at this size)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import TransformerConfig, TransformerLM
    from mxnet_tpu.models.transformer import make_train_step

    before = _select_counts()
    t0 = time.perf_counter()
    model = TransformerLM(TransformerConfig(**cfg_kw))
    params = model.init(jax.random.PRNGKey(0))
    velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = jax.jit(make_train_step(model, lr=lr))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg_kw["vocab_size"])
    x, y = tokens[:, :-1], tokens[:, 1:]
    params, velocity, loss = step(params, velocity, x, y)
    losses = [float(loss)]
    compile_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    for _ in range(steps):
        params, velocity, loss = step(params, velocity, x, y)
        losses.append(float(loss))
    run_s = time.perf_counter() - t1

    _check(all(np.isfinite(v) for v in losses),
           "non-finite LM loss: %r" % losses)
    _check(losses[-1] < losses[0],
           "LM loss did not fall on the fixed batch: %r" % losses)
    selected = _select_delta(before)
    if "mamba" not in cfg_kw.get("layer_types", ()):
        _check(not any(selected.pop("selective_scan").values()),
               "selective_scan selected by a model with no state-space layer")
    if not flash:
        _check(not any(selected.pop("flash_attention").values()),
               "flash_attention selected where the leg expects dense")
    for kernel, by_impl in selected.items():
        others = {i: n for i, n in by_impl.items()
                  if i != expect_impl and n}
        _check(by_impl[expect_impl] > 0 and not others,
               "kernel %s: expected only %r selections, counters moved by %r"
               % (kernel, expect_impl, by_impl))
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    return {"params_m": round(n_params / 1e6, 1), "batch": batch, "seq": seq,
            "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
            "steps": steps, "losses": [round(v, 4) for v in losses],
            "selected": {k: {i: n for i, n in v.items() if n}
                         for k, v in selected.items()}}


def leg_kernel_parity(interpret=False, seqs=(1024, 1000, 100, 1100),
                      head_dims=(64, 128), cell_shape=(4, 2048, 16, 128),
                      cross_seqs=(512, 1024), norm_shape=(4, 256, 2048),
                      xent_rows=1024, vocab=32000,
                      mm_shapes=((512, 768, 1024), (100, 70, 200)),
                      scan_shapes=((2, 300, 256, 16), (1, 1100, 640, 16)),
                      scan_cell_shape=(1, 4096, 5120, 16),
                      mla_shape=(1, 4096, 16, 192, 128),
                      gmm_shapes=((1000, 96, 40, (300, 0, 513, 100)),),
                      gmm_cell_shape=(24576, 2048, 1408,
                                      (384,) * 31 + (501,))):
    """Every Pallas kernel (compiled unless ``interpret``) against its lax
    reference on the same device, forward and gradient.

    The references run in float32 at ``highest`` matmul precision.  Fed
    float32, a kernel runs at ``highest`` too: a float32 dot inside a
    kernel follows ``jax.default_matmul_precision`` like any XLA dot, which
    on the TPU means bfloat16 passes by default (measured on the v5e: 3e-3
    relative, against 1e-5 here).  Tolerances are then the CPU suite's,
    widened by ``test_utils._device_tolerance_floor``.  Fed bfloat16, the
    way the models call it, a kernel runs at the default precision against
    the float32 reference on the upcast inputs, within bfloat16's
    resolution of the largest reference value (the output and its
    cotangent are rounded to bfloat16, so sums that cancel carry an error
    of that size, not of their own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       flash_attention_lse)
    from mxnet_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from mxnet_tpu.ops.pallas.int8_matmul import (int8_matmul,
                                                   int8_matmul_lax)
    from mxnet_tpu.ops.pallas.layers import (fused_rmsnorm,
                                              fused_softmax_xent)
    from mxnet_tpu.ops.pallas.selective_scan import selective_scan
    from mxnet_tpu.parallel.ring_attention import blockwise_attention
    from mxnet_tpu.test_utils import _device_tolerance_floor

    floor_r, floor_a = _device_tolerance_floor()
    failures, checked = [], 0
    t0 = time.perf_counter()

    def close(name, got, want, rtol, atol, of_max=0.0):
        """|got - want| <= atol + rtol * |want| + of_max * max |want|."""
        nonlocal checked
        got = jax.tree_util.tree_leaves(got)
        want = jax.tree_util.tree_leaves(want)
        rtol, atol = max(rtol, floor_r), max(atol, floor_a)
        for i, (g, w) in enumerate(zip(got, want)):
            checked += 1
            g = np.asarray(g, np.float64)
            w = np.asarray(w, np.float64)
            err = np.abs(g - w)
            bound = atol + rtol * np.abs(w) + of_max * np.abs(w).max()
            if not (np.all(np.isfinite(g)) and np.all(err <= bound)):
                failures.append(
                    "%s[%d]: max |err| %.3g against max |ref| %.3g "
                    "(rtol %g, atol %g, of_max %g)"
                    % (name, i, float(np.nanmax(err)),
                       float(np.abs(w).max()), rtol, atol, of_max))

    def out_and_grads(fn, weigh, argnums):
        """``fn``'s output and the gradient of a weighted sum of it, as one
        program."""
        def run(*args):
            def loss(*args):
                out = fn(*args)
                return weigh(out), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=argnums, has_aux=True)(*args)
            return out, grads
        return run

    def compare(tag, kernel, reference, args, weigh, argnums, fwd_tol=None,
                grad_tol=None):
        # fwd_tol / grad_tol are (rtol, atol) for float32 inputs
        bf16 = any(a.dtype == jnp.bfloat16 for a in args)
        with (contextlib.nullcontext() if bf16
              else jax.default_matmul_precision("highest")):
            got_out, got_grads = jax.jit(
                out_and_grads(kernel, weigh, argnums))(*args)
        with jax.default_matmul_precision("highest"):
            want_out, want_grads = jax.jit(
                out_and_grads(reference, weigh, argnums))(
                    *[a.astype(jnp.float32) if a.dtype == jnp.bfloat16
                      else a for a in args])
        if bf16:
            fwd_tol = grad_tol = (0.0, 0.0, 2e-2)
        close(tag + " fwd", got_out, want_out, *fwd_tol)
        close(tag + " grad", got_grads, want_grads, *grad_tol)

    def rand(seed, shape, dtype=jnp.float32):
        # host-side draws: no device program per input
        return jnp.asarray(
            np.random.RandomState(seed).standard_normal(shape), dtype)

    def weighted(w):
        return lambda out: (out.astype(jnp.float32) * w).sum()

    # -- flash attention: causal and not; aligned, ragged (one that the
    # larger tiles do not divide) and sub-tile sequences; float32, and the
    # bfloat16 the models feed it, at the benchmark cell's own shape too;
    # queries and keys of different lengths ---------------------------------
    def attention(causal):
        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=causal,
                                   interpret=interpret)

        def reference(q, k, v):
            return blockwise_attention(q, k, v, causal=causal)
        return kernel, reference

    def dense_reference(causal):
        # blockwise_attention takes one length; this one takes two, with
        # the kernels' top-left-aligned causal mask (q_pos >= k_pos)
        def reference(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
            if causal:
                keep = (jnp.arange(q.shape[1])[:, None]
                        >= jnp.arange(k.shape[1])[None, :])
                s = jnp.where(keep, s, -1e30)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return reference

    for T in seqs:
        for D in head_dims:
            qkv = [rand(i, (2, T, 2, D)) for i in (1, 2, 3)]
            w = weighted(rand(4, (2, T, 2, D)))
            for causal in (True, False):
                compare("flash_attention T=%d D=%d causal=%s"
                        % (T, D, causal), *attention(causal), qkv, w,
                        (0, 1, 2), (2e-5, 2e-5), (2e-4, 2e-4))
    T = seqs[0]
    for shape in [(2, T, 2, D) for D in head_dims] + [cell_shape]:
        qkv = [rand(i, shape, jnp.bfloat16) for i in (1, 2, 3)]
        compare("flash_attention bfloat16 %s" % "x".join(map(str, shape)),
                *attention(True), qkv, weighted(rand(4, shape)), (0, 1, 2))
    Tq, Tk = cross_seqs
    D = head_dims[-1]
    for causal in (True, False):
        for tq, tk in ((Tq, Tk), (Tk, Tq)):
            compare("flash_attention Tq=%d Tk=%d D=%d causal=%s"
                    % (tq, tk, D, causal),
                    attention(causal)[0], dense_reference(causal),
                    [rand(1, (2, tq, 2, D))]
                    + [rand(i, (2, tk, 2, D)) for i in (2, 3)],
                    weighted(rand(4, (2, tq, 2, D))), (0, 1, 2),
                    (2e-5, 2e-5), (2e-4, 2e-4))

    # -- flash_attention_lse: a loss on both outputs -----------------------
    D = head_dims[0]
    w, u = rand(4, (2, T, 2, D)), rand(5, (2, 2, T))
    compare("flash_attention_lse T=%d D=%d" % (T, D),
            lambda q, k, v: flash_attention_lse(q, k, v, causal=True,
                                                interpret=interpret),
            lambda q, k, v: blockwise_attention(q, k, v, causal=True,
                                                return_lse=True),
            [rand(i, (2, T, 2, D)) for i in (1, 2, 3)],
            lambda out: (out[0] * w).sum() + (out[1] * u).sum(),
            (0, 1, 2), (2e-5, 2e-5), (2e-4, 2e-4))

    # -- fused_rmsnorm ------------------------------------------------------
    def norm_reference(x, scale, eps=1e-6):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale

    for dtype in (jnp.float32, jnp.bfloat16):
        compare("fused_rmsnorm %s" % jnp.dtype(dtype).name,
                lambda x, scale: fused_rmsnorm(x, scale,
                                               interpret=interpret),
                norm_reference,
                [rand(6, norm_shape, dtype),
                 jnp.asarray(1.0 + 0.1 * np.random.RandomState(7)
                             .standard_normal(norm_shape[-1:]), dtype)],
                weighted(rand(8, norm_shape)), (0, 1), (1e-5, 1e-5),
                (1e-4, 1e-4))

    # -- fused_softmax_xent at the LM's vocabulary (pads to a 128 multiple) --
    def xent_reference(logits, labels):
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return lse - gold

    # a cotangent large enough that the per-class probabilities stand well
    # above the absolute tolerance, not only the one-hot entries
    rng = np.random.RandomState(0)
    g = jnp.asarray(1e3 * (1.0 + rng.rand(xent_rows)), jnp.float32)
    compare("fused_softmax_xent V=%d" % vocab,
            lambda logits, labels: fused_softmax_xent(logits, labels,
                                                      interpret=interpret),
            xent_reference,
            [jnp.asarray(2.0 * rng.standard_normal((xent_rows, vocab)),
                         jnp.float32),
             jnp.asarray(rng.randint(0, vocab, (xent_rows,)), jnp.int32)],
            lambda out: (out * g).sum(), (0,), (1e-5, 1e-5), (1e-4, 1e-5))

    # -- selective_scan: against a step-by-step lax.scan from a zero state,
    # every gradient (u, delta, A, B, C, D, z); lengths the chunk does not
    # divide, several chunks (the carried state and the reverse pass), and
    # the benchmark cell's own shape in the types the model feeds it --------
    def scan_reference(u, delta, A, B, C, D, z):
        def step(h, xs):
            ut, dt, bt, ct = xs
            h = (jnp.exp(dt[..., None] * A) * h
                 + (dt * ut)[..., None] * bt[:, None, :])
            return h, jnp.einsum("bdn,bn->bd", h, ct) + D * ut
        h0 = jnp.zeros((u.shape[0],) + A.shape, jnp.float32)
        y = jax.lax.scan(step, h0, tuple(
            x.transpose(1, 0, 2) for x in (u, delta, B, C)))[1]
        return y.transpose(1, 0, 2) * z * jax.nn.sigmoid(z)

    def scan_args(Bt, T, Di, N, dtype):
        wide, narrow = (Bt, T, Di), (Bt, T, N)
        return [rand(11, wide, dtype),
                jax.nn.softplus(rand(12, wide) - 2.0),
                -jnp.exp(0.5 * rand(13, (Di, N))),
                rand(14, narrow, dtype), rand(15, narrow, dtype),
                rand(16, (Di,)), rand(17, wide, dtype)]

    for shape in scan_shapes:
        compare("selective_scan %s" % "x".join(map(str, shape)),
                lambda *a: selective_scan(*a, interpret=interpret),
                scan_reference, scan_args(*shape, jnp.float32),
                weighted(rand(18, shape[:3])), tuple(range(7)),
                (2e-5, 2e-5), (2e-4, 2e-4))
    compare("selective_scan bfloat16 %s"
            % "x".join(map(str, scan_cell_shape)),
            lambda *a: selective_scan(*a, interpret=interpret),
            scan_reference, scan_args(*scan_cell_shape, jnp.bfloat16),
            weighted(rand(18, scan_cell_shape[:3])), tuple(range(7)))

    # -- flash attention at a value width of its own (latent attention:
    # scores over D, values over Dv), bfloat16, against dense attention ----
    Bm, Tm, Hm, Dm, Dvm = mla_shape
    mla_scale = 1.58963 * Dm ** -0.5

    def dense_two_widths(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * mla_scale
        s = jnp.where(jnp.tril(jnp.ones((Tm, Tm), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    compare("flash_attention bfloat16 %dx%dx%dx%d/%d" % mla_shape,
            lambda q, k, v: flash_attention(q, k, v, scale=mla_scale,
                                            interpret=interpret),
            dense_two_widths,
            [rand(20, (Bm, Tm, Hm, Dm), jnp.bfloat16),
             rand(21, (Bm, Tm, Hm, Dm), jnp.bfloat16),
             rand(22, (Bm, Tm, Hm, Dvm), jnp.bfloat16)],
            weighted(rand(23, (Bm, Tm, Hm, Dvm))), (0, 1, 2))

    # -- grouped_matmul: against every group's rows, masked, by its matrix,
    # one group after another; an empty group, sizes off the tile, rows past
    # the last group; float32, and the expert cell's own shape in bfloat16 --
    for (M, K, N, sizes), dtype in ([(g, jnp.float32) for g in gmm_shapes]
                                    + [(gmm_cell_shape, jnp.bfloat16)]):
        gs = jnp.asarray(sizes, jnp.int32)
        group_of_row = jnp.searchsorted(jnp.cumsum(gs), jnp.arange(M),
                                        side="right")

        def group_by_group(x, w, group_of_row=group_of_row):
            def add(acc, gw):
                g, wg = gw
                return acc + jnp.where((group_of_row == g)[:, None], x,
                                       0.0) @ wg, None
            return jax.lax.scan(add, jnp.zeros((x.shape[0], w.shape[2])),
                                (jnp.arange(w.shape[0]), w))[0]

        compare("grouped_matmul %s %dx%dx%d" % (jnp.dtype(dtype).name, M, K,
                                                N),
                lambda x, w, gs=gs: grouped_matmul(x, w, gs,
                                                   interpret=interpret),
                group_by_group,
                [rand(24, (M, K), dtype),
                 (rand(25, (len(sizes), K, N)) / K ** 0.5).astype(dtype)],
                weighted(rand(26, (M, N))), (0, 1), (2e-5, 2e-5),
                (2e-4, 2e-4))

    # -- int8_matmul: int32 path bit-identical, fused dequant close --------
    for M, N, K in mm_shapes:
        a = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
        b = jnp.asarray(rng.randint(-127, 128, (N, K)), jnp.int8)
        sa = jnp.float32(0.05)
        sb = jnp.asarray(rng.rand(N).astype(np.float32) * 0.1 + 0.01)
        tag = "int8_matmul %dx%dx%d" % (M, N, K)
        got = jax.jit(lambda a, b: int8_matmul(a, b, interpret=interpret))(
            a, b)
        checked += 1
        if not np.array_equal(np.asarray(got),
                              np.asarray(jax.jit(int8_matmul_lax)(a, b))):
            failures.append(tag + " int32: not bit-identical to the XLA "
                            "lowering")
        close(tag + " fused dequant",
              jax.jit(lambda a, b, sa, sb: int8_matmul(
                  a, b, sa, sb, interpret=interpret))(a, b, sa, sb),
              jax.jit(int8_matmul_lax)(a, b, sa, sb), 1e-5, 1e-4)

    _check(not failures, "%d of %d kernel parity checks failed:\n  %s"
           % (len(failures), checked, "\n  ".join(failures)))
    return {"checks": checked, "interpret": bool(interpret),
            "run_s": round(time.perf_counter() - t0, 1)}


# ---------------------------------------------------------------------------
# leg C — generation server
# ---------------------------------------------------------------------------
def leg_server(cfg_kw, n_requests=8, prompt_lens=(32, 512), max_new=32,
               max_seq_len=1024, prefill_buckets="64,256,512",
               max_slots=8, page_size=16):
    """``GenerationServer`` over the LM in its own dtype: every request
    completes with ``max_new`` valid ids, a repeated prompt repeats its
    tokens, nothing recompiles after ``warm()``, and ``drain()`` leaves the
    page allocator empty."""
    import jax
    import numpy as np

    from mxnet_tpu import dispatch, profiler
    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM

    vocab = cfg_kw["vocab_size"]
    t0 = time.perf_counter()
    model = TransformerLM(TransformerConfig(**cfg_kw))
    params = model.init(jax.random.PRNGKey(0))
    pages_per_seq = -(-max_seq_len // page_size)
    gcfg = GenerationConfig(page_size=page_size,
                            max_pages=max_slots * pages_per_seq + 1,
                            max_slots=max_slots, max_new_tokens=max_new,
                            max_seq_len=max_seq_len,
                            slot_buckets=str(max_slots),
                            prefill_buckets=prefill_buckets,
                            temperature=0.0)
    srv = GenerationServer(model, params, gcfg)       # warm() compiles
    compile_s = time.perf_counter() - t0
    try:
        rng = np.random.RandomState(0)
        lo, hi = prompt_lens
        lens = np.linspace(lo, hi, n_requests - 1).astype(int)
        prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
                   for n in lens]
        prompts.append(prompts[0].copy())     # the same prompt, twice
        base = profiler.dispatch_value("recompile")
        t1 = time.perf_counter()
        futs = [srv.submit_async(p, max_new_tokens=max_new) for p in prompts]
        outs = [list(f.result(timeout=600)) for f in futs]
        run_s = time.perf_counter() - t1
        recompiles = profiler.dispatch_value("recompile") - base
    finally:
        drained = srv.drain(timeout=60)
    for i, toks in enumerate(outs):
        _check(len(toks) == max_new and all(0 <= t < vocab for t in toks),
               "request %d: expected %d ids in [0, %d), got %r"
               % (i, max_new, vocab, toks))
    _check(outs[0] == outs[-1],
           "the same prompt gave different tokens: %r vs %r"
           % (outs[0], outs[-1]))
    _check(recompiles == 0, "%d recompile(s) after warm(): %s"
           % (recompiles, dispatch.explain_recompiles()))
    _check(drained and srv.engine.allocator.used == 0,
           "after drain(): drained=%r, %d page(s) still allocated"
           % (drained, srv.engine.allocator.used))
    return {"requests": len(prompts), "new_tokens": max_new,
            "prefill_chain": list(srv.engine.prefill_chain),
            "slot_chain": list(srv.engine.slot_chain),
            "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
            "recompiles_after_warm": recompiles,
            "kv_page_util_peak": round(srv.engine.allocator.peak_util, 4),
            "pages_used_after_drain": srv.engine.allocator.used}


def leg_server_reference(cfg_kw, prompt_len=40, max_new=16, page_size=16):
    """Paging and donation, checked on the device: the model in float32 at
    ``highest`` matmul precision, one prompt of several pages, greedy, equal
    token for token to a loop that re-runs the full forward with no cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM

    cfg_kw = dict(cfg_kw, dtype="float32")
    vocab = cfg_kw["vocab_size"]
    total = prompt_len + max_new
    _check(prompt_len >= 2 * page_size, "the prompt must span >= 2 pages")
    t0 = time.perf_counter()
    # a global setting, not the context manager: the server's scheduler
    # thread must trace and look up its programs under the same precision
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        model = TransformerLM(TransformerConfig(**cfg_kw))
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.random.RandomState(1).randint(
            0, vocab, size=prompt_len).astype(np.int32)
        pages = -(-total // page_size)
        gcfg = GenerationConfig(page_size=page_size, max_pages=pages + 2,
                                max_slots=1, max_new_tokens=max_new,
                                max_seq_len=pages * page_size,
                                slot_buckets="1",
                                prefill_buckets=str(pages * page_size),
                                temperature=0.0)
        srv = GenerationServer(model, params, gcfg)
        try:
            got = list(srv.submit(prompt, max_new_tokens=max_new,
                                  timeout=600))
        finally:
            srv.drain(timeout=60)

        # the model is causal, so one fixed-length program serves every step
        @jax.jit
        def next_token(params, tokens, n):
            logits, _ = model.apply(params, tokens)
            return jnp.argmax(logits[0, n - 1])

        tokens = np.zeros((1, total), np.int32)
        tokens[0, :prompt_len] = prompt
        want = []
        for n in range(prompt_len, total):
            tok = int(next_token(params, jnp.asarray(tokens), n))
            want.append(tok)
            tokens[0, n] = tok
    finally:
        jax.config.update("jax_default_matmul_precision", old)
    _check(got == want,
           "paged greedy decode differs from the no-cache forward:\n"
           "  server    %r\n  reference %r" % (got, want))
    return {"prompt_len": prompt_len, "new_tokens": max_new,
            "pages": pages, "tokens_equal": True,
            "run_s": round(time.perf_counter() - t0, 1)}


# ---------------------------------------------------------------------------
# leg D — four chips
# ---------------------------------------------------------------------------
def leg_multichip(cfg_kw, batch, seq, ref_loss, devices):
    """The LM train step on a dp=2 × tp=2 mesh over ``devices``: first-step
    loss equal to the single-device step (``ref_loss``) within 1e-3
    relative, and the parameters really spread over all four devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import TransformerConfig, TransformerLM
    from mxnet_tpu.models.transformer import default_rules, make_train_step
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.sharding import auto_shard

    _check(len(devices) == 4, "leg D wants 4 devices, got %d" % len(devices))
    before = _select_counts()
    t0 = time.perf_counter()
    model = TransformerLM(TransformerConfig(**cfg_kw))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg_kw["vocab_size"])
    x, y = tokens[:, :-1], tokens[:, 1:]
    with make_mesh(devices=devices, dp=2, tp=2):
        params = auto_shard(model.init(jax.random.PRNGKey(0)),
                            default_rules())
        velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
        step = jax.jit(make_train_step(model))
        params, velocity, loss = step(params, velocity, x, y)
        loss = float(loss)
    run_s = time.perf_counter() - t0

    _check(np.isfinite(loss)
           and abs(loss - ref_loss) <= 1e-3 * max(1.0, abs(ref_loss)),
           "mesh loss %r != single-device loss %r" % (loss, ref_loss))
    spread = set()
    for leaf in jax.tree_util.tree_leaves(params):
        spread |= set(leaf.sharding.device_set)
    _check(spread == set(devices),
           "parameters live on %d device(s): %s"
           % (len(spread), sorted(str(d) for d in spread)))
    in_use = {}
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            # only the CPU backend reports no memory statistics
            _check(d.platform == "cpu", "%s reports no memory_stats()" % d)
            continue
        in_use[str(d)] = int(stats["bytes_in_use"])
    full = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
    for name, nbytes in in_use.items():
        # params + velocity, the big matrices split two ways over tp
        _check(nbytes >= full // 2,
               "%s holds %d bytes against %d bytes of parameters: the model "
               "is not on it" % (name, nbytes, full))
    return {"mesh": {"dp": 2, "tp": 2}, "loss": round(loss, 4),
            "single_device_loss": round(ref_loss, 4),
            "param_devices": len(spread), "bytes_in_use": in_use,
            "selected": {k: {i: n for i, n in v.items() if n}
                         for k, v in _select_delta(before).items()},
            "run_s": round(run_s, 1)}


# ---------------------------------------------------------------------------
# the full-width run
# ---------------------------------------------------------------------------
def main():
    info = preflight()
    import jax

    import mxnet_tpu as mx

    legs = info["legs"] = {}
    t_start = time.perf_counter()

    def run(name, leg, *args, **kwargs):
        _say("%s ..." % name)
        legs[name] = leg(*args, **kwargs)
        _say("%s ok: %s" % (name, json.dumps(legs[name])))
        gc.collect()               # drop the leg's device buffers
        return legs[name]

    try:
        run("A_trainer", leg_trainer, mx.tpu())
        wide = run("B_lm_wide", leg_lm_train, LM_WIDE, batch=8, seq=1024,
                   flash=False)
        run("B_lm_long", leg_lm_train, LM_LONG, batch=1, seq=8192)
        # a fifth of the other legs' step: from a random start the mixers'
        # x_proj gradient is some forty times the other leaves', and at
        # 1e-2 with momentum the second step already overshoots
        run("B_lm_hybrid", leg_lm_train, LM_HYBRID, batch=1, seq=4096,
            lr=2e-3)
        run("B_kernel_parity", leg_kernel_parity)
        run("C_server", leg_server, LM_WIDE)
        run("C_server_reference", leg_server_reference, LM_WIDE)
        n_dev = len(jax.devices())
        if n_dev >= 4:
            run("D_multichip", leg_multichip, LM_WIDE, batch=8, seq=1024,
                ref_loss=wide["losses"][0], devices=jax.devices()[:4])
        else:
            info["multichip"] = "not run: %d device(s)" % n_dev
    except BaseException:
        # what was gathered before the failure, for whoever reads the log;
        # the exception still ends the run with a non-zero exit
        print("[chip_smoke] FAILED after %.0fs; gathered so far: %s"
              % (time.perf_counter() - t_start, json.dumps(info)),
              file=sys.stderr, flush=True)
        raise
    info["total_s"] = round(time.perf_counter() - t_start, 1)
    _say("record: %s" % json.dumps(info))
    # the last line is the verdict and the device, and nothing else
    print(json.dumps({"ok": True, "device": info["device"]}), flush=True)


if __name__ == "__main__":
    main()
