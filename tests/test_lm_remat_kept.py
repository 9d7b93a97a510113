"""What a rematerialised ``TransformerLM`` layer keeps of its forward
(``models.transformer.KEPT``): the results named there are not made a second
time for the backward, and every gradient is the bare ``jax.checkpoint``'s
to the bit.  And how many layers of a run one body of the layer loop holds
(``models.transformer._layers_a_body``)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models import TransformerConfig, TransformerLM
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import moe

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
    # gated experts, two slots a token, in every layer
    "experts": dict(TOY, mlp="swiglu", n_experts=4, moe_top_k=2,
                    mlp_types=("moe", "moe")),
    # runs of 5; of 3, 1, 2; of 1, 3
    "dense5": dict(TOY, n_layers=5),
    "hybrid6": dict(TOY, n_layers=6, ssm_state=8, ssm_dt_rank=4,
                    layer_types=("mamba",) * 3 + ("attention",)
                    + ("mamba",) * 2),
    "mlp_types4": dict(TOY, n_layers=4, n_experts=4, moe_top_k=2,
                       mlp_types=("dense",) + ("moe",) * 3),
}
RUNS = {"dense5": [("attention", 5)],
        "hybrid6": [("mamba", 3), ("attention", 1), ("mamba", 2)],
        "mlp_types4": [("dense", 1), ("moe", 3)]}


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
    ``w_down``; with ``KEPT`` it holds no flash forward kernel, no ``wqkv``,
    no ``wo``, no ``in_proj`` and no ``out_proj``.
    (The experts of ``use_moe`` have weights of their own, no ``w_down``.)"""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    mamba = int(kind == "hybrid")
    w_down = 0 if kind == "moe" else 1 + mamba
    assert forward_work(kind) == {
        "flash_fwd": 1, "wo": 1, "in_proj": mamba, "out_proj": mamba,
        "wqkv": 1, "w_down": w_down}
    monkeypatch.setattr(transformer, "KEPT", ())
    assert forward_work(kind) == {
        "flash_fwd": 2, "wo": 2, "in_proj": 2 * mamba, "out_proj": 2 * mamba,
        "wqkv": 2, "w_down": w_down}


def test_wqkvs_product_alone_is_kept_by_its_name(monkeypatch):
    """With ``KEPT`` cut to ``QKV_NAME`` only ``wqkv``'s product leaves the
    backward's body."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    monkeypatch.setattr(transformer, "KEPT", (transformer.QKV_NAME,))
    assert forward_work("dense") == {
        "flash_fwd": 2, "wo": 2, "in_proj": 0, "out_proj": 0, "wqkv": 1,
        "w_down": 1}


def expert_work():
    """Of the jaxpr of ``jax.grad(model.loss)`` of the gated expert toy:
    the grouped product's forward kernels, the row-gather kernels by name
    (``ops/pallas/moe_gather.py``) and the lax gathers whose operand has the
    down product's shape, ``[P, E]``."""
    model, p, x, y = build("experts")
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(p, x, y).jaxpr
    seen = {"gmm_fwd": 0, "moe_gather_rows": 0, "moe_slot_sum": 0,
            "moe_gather_grad": 0, "gathers_of_P_E_rows": 0}
    for eqn in equations(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            if name in seen:
                seen[name] += 1
        elif eqn.primitive.name == "gather":
            seen["gathers_of_P_E_rows"] += (
                eqn.invars[0].aval.shape == (B * T * 2, E))
    return seen


@pytest.mark.parametrize("gathers", ["kernels", "lax"])
@pytest.mark.parametrize("kept,products", [
    ("KEPT", 5), ("down_rows", 5), ("nothing", 6)])
def test_the_experts_down_rows_are_kept_and_gathered_once_a_pass(
        kept, products, gathers, monkeypatch):
    """One body stands for both expert layers.  The forward runs gate, up
    and down; the backward's body re-makes gate and up, and the down
    product too unless its rows are kept by name.  Kept or not, only two
    gathers read ``[P, E]`` rows by slot: the combine's forward and the
    dispatch's transpose -- the second forward gathers none for the
    combine, whose backward reads ``g`` by token.  With the row-gather
    kernels these are ``moe_slot_sum``, each pass gathers the dispatch's
    rows once (``moe_gather_rows``: the forward and the second forward) and
    the combine's backward is ``moe_gather_grad``; no lax gather is left.
    The lax forms (the CPU, a mesh, a buffer that fits the fast memory)
    gather ``out[inverse]`` and ``g[inverse]``."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    monkeypatch.setattr(transformer, "KEPT", {
        "KEPT": transformer.KEPT, "nothing": (),
        "down_rows": moe.SAVED_NAMES}[kept])
    if gathers == "lax":
        monkeypatch.setattr(moe.moe_gather, "select",
                            lambda *shape: "fallback")
        assert expert_work() == {
            "gmm_fwd": products, "moe_gather_rows": 0, "moe_slot_sum": 0,
            "moe_gather_grad": 0, "gathers_of_P_E_rows": 2}
    else:
        assert expert_work() == {
            "gmm_fwd": products, "moe_gather_rows": 2, "moe_slot_sum": 2,
            "moe_gather_grad": 1, "gathers_of_P_E_rows": 0}


def test_the_expert_lms_gradient_builds_no_token_major_slots():
    """Neither pass of ``jax.grad(model.loss)`` of the expert toy in
    bfloat16 holds a value of shape ``[S, k, E]``: the slots are gathered
    slot-major, ``[k, S, E]``, summed slice by slice (no float32 copy of
    them either), and the combine's backward stays in the sorted rows."""
    model, p, x, y = build("experts", dtype="bfloat16")
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(p, x, y).jaxpr
    shapes = {(v.aval.shape, str(v.aval.dtype))
              for eqn in equations(jaxpr) for v in eqn.outvars}
    assert ((2, B * T, E), "bfloat16") in shapes         # both gathers
    assert ((2, B * T, E), "float32") not in shapes
    assert not [s for s in shapes if s[0] == (B * T, 2, E)]


@pytest.mark.parametrize("kind,over,kept", [
    ("dense", {}, None), ("hybrid", {}, None), ("moe", {}, None),
    ("dense", dict(scan_unroll=False), None),
    ("hybrid", dict(scan_unroll=False), None),
    ("dense", {}, "wqkv"), ("hybrid", {}, "wqkv"), ("moe", {}, "wqkv"),
    ("experts", {}, None), ("experts", {}, "down_rows"),
    ("moe", {}, "down_rows"),
], ids=["dense", "hybrid", "moe", "dense_rolled", "hybrid_rolled",
        "dense_wqkv_alone", "hybrid_wqkv_alone", "moe_wqkv_alone",
        "experts", "experts_down_rows_alone", "moe_down_rows_alone"])
def test_every_gradient_is_the_bare_checkpoints_to_the_bit(kind, over, kept,
                                                           monkeypatch):
    """A kept value is the value the second forward would re-make: loss and
    every gradient leaf equal those of a policy that keeps nothing (the
    parent's dense branch), kernels through the interpreter; so with all of
    ``KEPT``, with ``wqkv``'s product alone and with the experts' down rows
    alone.  In a rolled
    layer loop the kept values are a scan's stacked residuals, and there
    XLA's CPU backend re-makes ``x + o`` fused otherwise than it made it
    the first time: with the mixer's output kept the backward sees the
    forward's own value, and the leaves agree to float32's last bits (5e-6
    of a leaf's largest entry at these sizes), not to the bit."""
    if kind != "moe":                      # the experts' body has no kernel
        monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    model, p, x, y = build(kind, **over)
    if kept:
        monkeypatch.setattr(transformer, "KEPT", {
            "wqkv": (transformer.QKV_NAME,),
            "down_rows": moe.SAVED_NAMES}[kept])
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
    from mxnet_tpu.models import mamba, short_conv
    flash_attention, selective_scan = (
        importlib.import_module("mxnet_tpu.ops.pallas." + name)
        for name in ("flash_attention", "selective_scan"))
    assert transformer.KEPT == (
        selective_scan.SAVED_NAMES + flash_attention.SAVED_NAMES
        + (mamba.IN_PROJ_NAME, transformer.QKV_NAME, transformer.MIXER_OUT)
        + moe.SAVED_NAMES + (short_conv.IN_PROJ_NAME,))
    assert len(set(transformer.KEPT)) == 9


# -- the layer loop ---------------------------------------------------------
def layer_loops(kind, **over):
    """The layer scans of the forward as ``[(length, unroll)]``, and what
    tracing it added to the ``lm.layers.*`` counters."""
    from mxnet_tpu import telemetry

    def counters():
        return {k: v for k, v in
                telemetry.registry().snapshot()["counters"].items()
                if k.startswith("lm.layers.")}

    model, p, x, y = build(kind, **over)
    before = counters()
    jaxpr = jax.make_jaxpr(model.loss)(p, x, y).jaxpr
    counted = {k: v - before.get(k, 0) for k, v in counters().items()
               if v != before.get(k, 0)}
    loops = [(eqn.params["length"], eqn.params["unroll"])
             for eqn in jaxpr.eqns if eqn.primitive.name == "scan"]
    return loops, counted


@pytest.mark.parametrize("kind", sorted(RUNS))
@pytest.mark.parametrize("bound", [1, 2, 4, None],
                         ids=["1", "2", "4", "module"])
def test_a_run_longer_than_the_bound_is_a_loop_of_one_layer_a_body(
        kind, bound, monkeypatch):
    """``layers()`` unrolls a run of n equal layers whole (what
    ``unroll=True`` gave) where n is at most ``_UNROLLED_RUN``, and scans a
    longer one a layer a body.  The module's own bound leaves these toys'
    runs whole."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    if bound:
        monkeypatch.setattr(transformer, "_UNROLLED_RUN", bound)
    bound = transformer._UNROLLED_RUN
    bodies = [(run_kind, n, n if n <= bound else 1)
              for run_kind, n in RUNS[kind]]
    loops, counted = layer_loops(kind)
    assert loops == [(n, body) for _, n, body in bodies]
    want = {}
    for run in bodies:
        name = "lm.layers.%s.%dx%d" % run
        want[name] = want.get(name, 0) + 1
    assert counted == want


@pytest.mark.parametrize("kind", sorted(RUNS))
def test_scan_unroll_false_is_one_layer_a_body(kind, monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    loops, counted = layer_loops(kind, scan_unroll=False)
    assert loops == [(n, 1) for _, n in RUNS[kind]]
    assert sorted(counted) == sorted(
        {"lm.layers.%s.%dx1" % run for run in RUNS[kind]})


def test_the_benchmarks_runs_on_both_sides_of_the_bound():
    """The cells' runs of equal layers: the dense LM's one run of 24 is
    longer than the bound and loops a layer a body; the hybrid's (7, 1, 6)
    and the expert cell's (1, 4) are under it and stay unrolled whole."""
    body = transformer._layers_a_body
    assert [body(n) for n in (24, 7, 6, 4, 1)] == [1, 7, 6, 4, 1]
    assert [body(n, False) for n in (24, 7, 1)] == [1, 1, 1]


@pytest.mark.parametrize("kind", sorted(RUNS))
def test_loss_and_gradients_are_alike_at_every_body_length(kind,
                                                           monkeypatch):
    """Bodies of 1, 2 and all of a run's layers are one computation: the
    loss and every gradient leaf agree (float32 on the CPU, to 1e-6 of the
    leaf's largest entry: XLA fuses a loop's body otherwise than the
    unrolled layers)."""
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    model, p, x, y = build(kind)
    got = {}
    for body in (1, 2, 8):
        monkeypatch.setattr(transformer, "_layers_a_body",
                            lambda n, unrolled=True: min(n, body))
        got[body] = jax.jit(jax.value_and_grad(model.loss))(p, x, y)
    loss, grads = got[8]
    for body in (1, 2):
        np.testing.assert_allclose(got[body][0], loss, rtol=1e-6)
        for name in grads:
            top = float(jnp.abs(grads[name]).max())
            assert top > 0, name
            np.testing.assert_allclose(got[body][1][name], grads[name],
                                       rtol=0, atol=1e-6 * top,
                                       err_msg=name)
