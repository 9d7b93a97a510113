"""What a rematerialised ``TransformerLM`` layer keeps of its forward
(``models.transformer.KEPT``): the results named there are not made a second
time for the backward, and every gradient is the bare ``jax.checkpoint``'s
to the bit."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models import TransformerConfig, TransformerLM
from mxnet_tpu.models import transformer

# widths that tell the weights apart by their shapes: wqkv [E, 96], wo
# [E, E], w_up [E, 80], in_proj [E, 128], out_proj [64, E], w_down [80, E],
# the head [E, 160]
E, F, B, T = 32, 80, 2, 32
TOY = dict(vocab_size=160, d_model=E, n_heads=4, n_layers=2, d_ff=F,
           max_len=64, dtype="float32", dense_attn_max_score_mb=0)
MODELS = {
    "dense": TOY,
    "hybrid": dict(TOY, layer_types=("mamba", "attention"), ssm_state=8,
                   ssm_dt_rank=4),
    "moe": dict(TOY, use_moe=True, n_experts=4),
}


def build(kind, **over):
    model = TransformerLM(TransformerConfig(**dict(MODELS[kind], **over)))
    t = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, 128)
    return model, model.init(jax.random.PRNGKey(0)), t[:, :-1], t[:, 1:]


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def forward_work(kind):
    """Of the jaxpr of ``jax.grad(model.loss)``: how often each weight's
    forward product ``[B, T, f] x [f, g]`` stands there (its transposes
    contract other dimensions) and how many flash forward kernels."""
    model, p, x, y = build(kind)
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(p, x, y).jaxpr
    weights = {"wo": (E, E), "in_proj": (E, 4 * E), "out_proj": (2 * E, E),
               "wqkv": (E, 3 * E), "w_down": (F, E)}
    seen = dict.fromkeys(list(weights) + ["flash_fwd"], 0)
    for eqn in equations(jaxpr):
        if eqn.primitive.name == "pallas_call":
            seen["flash_fwd"] += eqn.params["name"] == "flash_fwd"
        elif eqn.primitive.name == "dot_general":
            (lhs, rhs), batch = eqn.params["dimension_numbers"]
            for leaf, shape in weights.items():
                seen[leaf] += ((lhs, rhs, batch) == ((2,), (0,), ((), ()))
                               and eqn.invars[1].aval.shape == shape)
    return seen


@pytest.mark.parametrize("kind", ["dense", "hybrid", "moe"])
def test_a_kept_result_is_not_made_a_second_time(kind, monkeypatch):
    """A scan body stands once in the forward and once in the backward of
    the jaxpr, whatever the layers it runs over.  With nothing kept (a bare
    ``jax.checkpoint``) the backward's body holds the whole forward but
    ``w_down``; with ``KEPT`` it holds no flash forward kernel, no ``wo``,
    no ``in_proj`` and no ``out_proj`` -- ``wqkv`` is re-made as before.
    (The experts of ``use_moe`` have weights of their own, no ``w_down``.)"""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    mamba = int(kind == "hybrid")
    w_down = 0 if kind == "moe" else 1 + mamba
    assert forward_work(kind) == {
        "flash_fwd": 1, "wo": 1, "in_proj": mamba, "out_proj": mamba,
        "wqkv": 2, "w_down": w_down}
    monkeypatch.setattr(transformer, "KEPT", ())
    assert forward_work(kind) == {
        "flash_fwd": 2, "wo": 2, "in_proj": 2 * mamba, "out_proj": 2 * mamba,
        "wqkv": 2, "w_down": w_down}


@pytest.mark.parametrize("kind,over", [
    ("dense", {}), ("hybrid", {}), ("moe", {}),
    ("dense", dict(scan_unroll=False)), ("hybrid", dict(scan_unroll=False)),
], ids=["dense", "hybrid", "moe", "dense_rolled", "hybrid_rolled"])
def test_every_gradient_is_the_bare_checkpoints_to_the_bit(kind, over,
                                                           monkeypatch):
    """A kept value is the value the second forward would re-make: loss and
    every gradient leaf equal those of a policy that keeps nothing (the
    parent's dense branch), kernels through the interpreter.  In a rolled
    layer loop the kept values are a scan's stacked residuals, and there
    XLA's CPU backend re-makes ``x + o`` fused otherwise than it made it
    the first time: with the mixer's output kept the backward sees the
    forward's own value, and the leaves agree to float32's last bits (5e-6
    of a leaf's largest entry at these sizes), not to the bit."""
    if kind != "moe":                      # the experts' body has no kernel
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    model, p, x, y = build(kind, **over)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(p, x, y)
    monkeypatch.setattr(transformer, "KEPT", ())
    loss0, grads0 = jax.jit(jax.value_and_grad(model.loss))(p, x, y)
    assert float(loss) == float(loss0)
    assert set(grads) == set(p)
    for name in grads:
        top = float(jnp.abs(grads[name]).max())
        assert top > 0, name
        np.testing.assert_allclose(grads[name], grads0[name], rtol=0,
                                   atol=2e-5 * top if over else 0,
                                   err_msg=name)


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
def test_both_entry_points_of_flash_name_their_forward(with_lse):
    """``flash_attention`` and ``flash_attention_lse`` (ring attention's
    step) under a policy that keeps ``SAVED_NAMES``: one forward kernel in
    the jaxpr of the gradient, two under a policy that keeps nothing, and
    the same gradients to the bit."""
    fa = importlib.import_module(
        "mxnet_tpu.ops.pallas.flash_attention")
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, T, 4, 8))
               for i in range(3))

    def loss(q, k, v):
        if with_lse:
            o, lse = fa.flash_attention_lse(q, k, v, interpret=True)
            return jnp.sum(o * o) + jnp.sum(jnp.sin(lse))
        return jnp.sum(jnp.square(fa.flash_attention(q, k, v, interpret=True)))

    def grad(names):
        return jax.grad(jax.checkpoint(
            loss, policy=jax.checkpoint_policies.save_only_these_names(
                *names)), argnums=(0, 1, 2))

    def forwards(names):
        return sum(eqn.primitive.name == "pallas_call"
                   and eqn.params["name"] == "flash_fwd" for eqn in
                   equations(jax.make_jaxpr(grad(names))(q, k, v).jaxpr))

    assert (forwards(fa.SAVED_NAMES), forwards(())) == (1, 2)
    for got, want in zip(jax.jit(grad(fa.SAVED_NAMES))(q, k, v),
                         jax.jit(grad(()))(q, k, v)):
        assert float(jnp.abs(got).max()) > 0
        np.testing.assert_array_equal(got, want)


def test_the_names_are_one_tuple():
    from mxnet_tpu.models import mamba
    flash_attention, selective_scan = (
        importlib.import_module("mxnet_tpu.ops.pallas." + name)
        for name in ("flash_attention", "selective_scan"))
    assert transformer.KEPT == (
        selective_scan.SAVED_NAMES + flash_attention.SAVED_NAMES
        + (mamba.IN_PROJ_NAME, transformer.MIXER_OUT))
    assert len(set(transformer.KEPT)) == 6
