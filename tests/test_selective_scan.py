"""The selective-scan kernel (`ops/pallas/selective_scan.py`): the Pallas
kernels through the interpreter and the chunked lax fallback against a
step-by-step ``lax.scan`` from a zero state, forward and every gradient
(``u``, ``delta``, ``B``, ``C``, ``A_log``, ``D``, ``z``); the block chooser;
which implementation the entry point takes and the trace-time telemetry."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu import telemetry
from mxnet_tpu.ops.pallas import kernel_impl

ss = importlib.import_module("mxnet_tpu.ops.pallas.selective_scan")

NAMES = ("u", "delta", "A_log", "B", "C", "D", "z")


def step_by_step(u, delta, A_log, B, C, D, z):
    """The recurrence as it is written down: one step at a time."""
    A = -jnp.exp(A_log)

    def step(h, xs):
        ut, dt, bt, ct = xs
        h = (jnp.exp(dt[..., None] * A) * h
             + (dt * ut)[..., None] * bt[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, ct) + D * ut

    h0 = jnp.zeros((u.shape[0],) + A.shape, u.dtype)
    y = lax.scan(step, h0, tuple(x.transpose(1, 0, 2)
                                 for x in (u, delta, B, C)))[1]
    return y.transpose(1, 0, 2) * z * jax.nn.sigmoid(z)


def operands(Bt, T, Di, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    wide, narrow = (Bt, T, Di), (Bt, T, N)
    args = (jax.random.normal(ks[0], wide),
            jax.nn.softplus(jax.random.normal(ks[1], wide) - 1.0),
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))
            + 0.3 * jax.random.normal(ks[2], (Di, N)),
            jax.random.normal(ks[3], narrow),
            jax.random.normal(ks[4], narrow),
            jax.random.normal(ks[5], (Di,)),
            jax.random.normal(ks[6], wide))
    return args, jax.random.normal(ks[7], wide)


def out_and_grads(fn, args, w):
    def loss(*a):
        out = fn(*a)
        return jnp.sum(out * w), out
    (_, out), grads = jax.value_and_grad(loss, argnums=tuple(range(7)),
                                         has_aux=True)(*args)
    return out, grads


def scan_with(impl, chunk):
    def fn(u, delta, A_log, B, C, D, z):
        A = -jnp.exp(A_log)
        if impl == "lax":
            return ss.selective_scan_lax(u, delta, A, B, C, D, z, chunk=chunk)
        return ss.selective_scan(u, delta, A, B, C, D, z, chunk=chunk,
                                 interpret=True)
    return fn


# (batch, T, Di, N, chunk)
SHAPES = [
    pytest.param(2, 40, 128, 16, 16, id="T40_not_a_multiple_of_chunk16"),
    pytest.param(1, 64, 128, 16, 16, id="T64_four_chunks_state_carried"),
    pytest.param(1, 50, 200, 16, 32, id="Di200_padded_to_256_two_blocks"),
    pytest.param(1, 33, 128, 4, 16, id="N4_padded_to_a_sublane_tile"),
    # C_t h_t is summed over the sublane tiles inside the sequential loop
    pytest.param(1, 40, 128, 8, 16, id="N8_one_sublane_tile"),
    pytest.param(1, 40, 128, 12, 16, id="N12_padded_to_two_sublane_tiles"),
    pytest.param(1, 24, 128, 24, 16, id="N24_three_sublane_tiles"),
    # dB and dC leave on the lanes of [N, 128] tiles: a chunk that fills part
    # of one tile, and one of two whole tiles
    pytest.param(2, 100, 128, 8, 48, id="chunk48_part_of_a_tile_of_columns"),
    pytest.param(1, 300, 128, 8, 256, id="chunk256_two_tiles_of_columns"),
]


@pytest.mark.parametrize("impl", ["interpret", "lax"])
@pytest.mark.parametrize("Bt,T,Di,N,chunk", SHAPES)
def test_scan_matches_step_by_step_forward_and_every_gradient(
        Bt, T, Di, N, chunk, impl):
    args, w = operands(Bt, T, Di, N)
    want_out, want = out_and_grads(step_by_step, args, w)
    got_out, got = out_and_grads(scan_with(impl, chunk), args, w)
    # float32 throughout; the orders of summation differ (chunks, bulk
    # reductions over lanes), nothing else
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-5)
    for name, g, r in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(r))) + 1e-6
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(r) / scale, rtol=0,
                                   atol=2e-6, err_msg="d" + name)


def test_decay_from_nearly_none_to_nearly_all_in_one_sequence():
    """``dA`` and ``ddelta`` are made of the state as it came into a step,
    ``exp(delta_t A) h_{t-1}``.  The backward keeps that, and re-makes ``h_t``
    as that plus ``delta_t u_t B_t``; the other way round, ``h_t - delta_t u_t
    B_t``, is one float32 rounding of ``h_t`` off where the state is
    replaced.  With ``delta |A|`` from 1e-4 (the state stands) to 30 (it is
    replaced) in one sequence, every gradient is held against float64."""
    Bt, T, Di, N = 1, 48, 128, 16
    args, w = operands(Bt, T, Di, N, seed=3)
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    # |A| = 1 .. 15, delta log-uniform: delta |A| from 1e-4 to 30, both
    # ends in every channel's sequence
    delta = 10.0 ** jax.random.uniform(ks[0], (Bt, T, Di), minval=-4.0,
                                       maxval=jnp.log10(2.0))
    delta = delta.at[:, 5::12].set(2.0).at[:, 6::12].set(1e-4)
    A_log = jnp.log(jnp.linspace(1.0, 15.0, N))[None, :] * jnp.ones((Di, 1))
    args = (args[0], delta, A_log) + args[3:]
    reach = delta[..., None] * jnp.exp(A_log)
    assert float(reach.min()) <= 1e-4 and float(reach.max()) >= 30.0
    with jax.enable_x64(True):
        want_out, want = out_and_grads(
            step_by_step, [jnp.asarray(np.asarray(a), jnp.float64)
                           for a in args], jnp.asarray(np.asarray(w),
                                                       jnp.float64))
        want_out, want = np.asarray(want_out), [np.asarray(g) for g in want]
    got_out, got = out_and_grads(scan_with("interpret", 16), args, w)
    plain_out, plain = out_and_grads(step_by_step, args, w)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-5)
    for name, g, p, r in zip(NAMES, got, plain, want):
        scale = float(np.max(np.abs(r)))
        err = float(np.max(np.abs(np.asarray(g) - r))) / scale
        # float32 all through: no further from float64 than a few roundings,
        # and no further than twice what the recurrence written step by
        # step in float32 is
        plain_err = float(np.max(np.abs(np.asarray(p) - r))) / scale
        assert err <= max(2.0 * plain_err, 1e-6), (name, err, plain_err)


def test_kernel_and_fallback_agree_across_a_chunk_boundary_only_state():
    """An input that is zero after the first chunk: everything later is the
    carried state alone, decaying."""
    args, w = operands(1, 48, 128, 16)
    u = args[0].at[:, 16:].set(0.0)
    args = (u,) + args[1:]
    want = step_by_step(*args)
    assert float(jnp.max(jnp.abs(want[:, 16:] - 0.0))) > 1e-3
    got = scan_with("interpret", 16)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_bfloat16_inputs_keep_their_type_and_stay_close():
    args, _w = operands(1, 40, 128, 16)
    A = -jnp.exp(args[2])
    low = [a.astype(jnp.bfloat16) for a in args]
    out = ss.selective_scan(low[0], args[1], A, low[3], low[4], args[5],
                            low[6], chunk=16, interpret=True)
    assert out.dtype == jnp.bfloat16
    want = step_by_step(*[a.astype(jnp.float32) if i in (0, 3, 4, 6) else
                          args[i] for i, a in enumerate(low)])
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - want))
    assert float(err) <= 2e-2 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,Di,N,want", [
    (4096, 5120, 16, (128, 1024)),      # the benchmark cell's scan
    (4096, 2048, 16, (128, 1024)),      # chip_smoke's hybrid leg
    (4096, 1536, 16, (256, 512)),
    (4096, 640, 16, (256, 128)),
    (40, 128, 16, (32, 128)),           # a short sequence pads to 48 at most
    (8192, 5120, 64, (32, 1024)),
    (4096, 5120, 8, (256, 1024)),       # half the states, twice the chunk
    (4096, 4096, 128, (16, 1024)),
])
def test_blocks_come_from_the_shape(T, Di, N, want):
    chunk, d_block = ss._choose_blocks(T, Di, N)
    assert (chunk, d_block) == want
    assert chunk % ss._ROWS == 0 and d_block % 128 == 0
    # a step's tile and the rows left empty after it (``_PAD``)
    assert chunk * (max(N, 8) + ss._PAD) * d_block * 4 <= ss._STATE_SCRATCH
    # and the longest chunk that does
    assert (chunk == 256 or 2 * chunk > -(-T // ss._ROWS) * ss._ROWS
            or 2 * chunk * (max(N, 8) + ss._PAD) * d_block * 4
            > ss._STATE_SCRATCH)


def test_the_pitch_of_a_strided_access_is_four_mod_eight():
    """Eight rows a pitch apart fall in eight of the 32 banks only if the
    pitch is 4 mod 8 (or odd): what ``_PAD`` is for, whatever ``N``."""
    for rows in (8, 16, 24, 64, ss._ROWS):
        pitch = rows + ss._PAD
        assert len({pitch * i % 32 for i in range(8)}) == 8, rows


@pytest.mark.parametrize("chunk", [24, 192])
def test_a_chunk_is_whole_loop_bodies_and_whole_tiles_of_columns(chunk):
    """24 is no multiple of the sixteen steps of a loop body; 192 is more
    than one [N, 128] tile of dB's columns and not whole tiles."""
    args, _w = operands(1, 32, 128, 8)
    with pytest.raises(ValueError, match="multiple"):
        ss.selective_scan(args[0], args[1], -jnp.exp(args[2]), *args[3:],
                          chunk=chunk, interpret=True)


def test_off_the_tpu_the_entry_point_is_the_lax_form(monkeypatch):
    assert kernel_impl("selective_scan") == "fallback"
    monkeypatch.setattr(ss, "selective_scan_lax", lambda *a, **kw: "lax")
    args, _w = operands(1, 16, 128, 8)
    assert ss.selective_scan(*args) == "lax"


def test_the_rule_and_trace_time_telemetry(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    reg = telemetry.registry()

    def count(name):
        return reg.snapshot()["counters"].get(name, 0)

    before = {k: count(k) for k in (
        "pallas.select.selective_scan.interpret",
        "pallas.ssm_scan.chunk.fwd.16x128",
        "pallas.ssm_scan.chunk.bwd.16x128")}
    args, w = operands(1, 16, 128, 8)
    A = -jnp.exp(args[2])
    jax.grad(lambda u: jnp.sum(
        ss.selective_scan(u, args[1], A, *args[3:]) * w))(args[0])
    for k, v in before.items():
        assert count(k) == v + 1, k
