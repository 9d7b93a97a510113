"""Weights of the hybrid state-space LM family, made by the benchmark from
``--seed`` in the flat layout the program's ``TransformerLM`` takes with
``layer_types``: leaves every layer has under ``blocks.`` (stacked over all
layers), the attention layers' own under ``attn.`` and the Mamba layers' own
under ``ssm.`` (each stacked over the layers of its kind).  The program and
the plain reference are both handed what is made here.

The start (the configuration file's ``assumed.init``): matrices, the
convolution's taps and its bias normal / sqrt(fan_in); norm scales and ``D``
one; ``A_log = log(1..N)`` in every channel; ``dt_bias`` the inverse softplus
of a step drawn log-uniformly from [1e-3, 1e-1] (Mamba's published start,
which keeps ``exp(delta A)`` well away from 0 and from 1).  ``A_log``, ``D``
and ``dt_bias`` are float32 whatever the model's type.
"""
import math

import jax
import jax.numpy as jnp

from . import weights

F32_LEAVES = ("ssm.A_log", "ssm.D", "ssm.dt_bias")
COMMON = ("ln1_scale", "ln2_scale", "w_gate", "w_up", "w_down")
ATTENTION = ("wqkv", "wo")
MAMBA = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm_scale",
         "b_norm_scale", "c_norm_scale", "dt_proj", "dt_bias", "A_log", "D",
         "out_proj")


def sizes(m):
    """The widths the leaves are cut from."""
    e = m["d_model"]
    return {"e": e, "f": m["d_ff"], "v": m["vocab_size"],
            "heads": m["n_heads"], "kv_heads": m["n_kv_heads"],
            "head_dim": e // m["n_heads"],
            "di": m["ssm_expand"] * e, "n": m["ssm_state"],
            "r": m["ssm_dt_rank"], "k": m["ssm_conv"],
            "layers": m["n_layers"],
            "n_attn": m["layer_types"].count("attention"),
            "n_mamba": m["layer_types"].count("mamba")}


def leaf_shapes(m):
    """{name: (shape, fan_in or a word for a leaf that is not drawn)}."""
    s = sizes(m)
    e, f, di, n, r, k = s["e"], s["f"], s["di"], s["n"], s["r"], s["k"]
    hd = s["heads"] * s["head_dim"]
    qkv = hd + 2 * s["kv_heads"] * s["head_dim"]
    la, lm, lay = s["n_attn"], s["n_mamba"], s["layers"]
    return {
        "embed": ((s["v"], e), e),
        "final_ln_scale": ((e,), "ones"),
        "blocks.ln1_scale": ((lay, e), "ones"),
        "blocks.ln2_scale": ((lay, e), "ones"),
        "blocks.w_gate": ((lay, e, f), e),
        "blocks.w_up": ((lay, e, f), e),
        "blocks.w_down": ((lay, f, e), f),
        "attn.wqkv": ((la, e, qkv), e),
        "attn.wo": ((la, hd, e), hd),
        "ssm.in_proj": ((lm, e, 2 * di), e),
        "ssm.conv_w": ((lm, k, di), k),
        "ssm.conv_b": ((lm, di), k),
        "ssm.x_proj": ((lm, di, r + 2 * n), di),
        "ssm.dt_norm_scale": ((lm, r), "ones"),
        "ssm.b_norm_scale": ((lm, n), "ones"),
        "ssm.c_norm_scale": ((lm, n), "ones"),
        "ssm.dt_proj": ((lm, r, di), r),
        "ssm.dt_bias": ((lm, di), "dt_bias"),
        "ssm.A_log": ((lm, di, n), "A_log"),
        "ssm.D": ((lm, di), "ones"),
        "ssm.out_proj": ((lm, di, e), di),
    }


def param_count(m):
    return sum(math.prod(shape) for shape, _f in leaf_shapes(m).values())


def _leaf(name, spec, key, dtype, index):
    shape, how = spec
    if name in F32_LEAVES:
        dtype = jnp.float32
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=jnp.float32)), shape).astype(dtype)
    k = jax.random.fold_in(key, index)
    if how == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(how)).astype(dtype)


def init(m, seed):
    """All leaves in one jitted call."""
    specs = leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    names = sorted(specs)

    def make(key):
        return {n: _leaf(n, specs[n], key, dtype, i)
                for i, n in enumerate(names)}

    return jax.jit(make)(weights.key_from_seed(seed))


def init_leaf(m, seed, name):
    """One leaf, the same values ``init`` gives it."""
    specs = leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    index = sorted(specs).index(name)
    fn = jax.jit(lambda key: _leaf(name, specs[name], key, dtype, index))
    return fn(weights.key_from_seed(seed))
