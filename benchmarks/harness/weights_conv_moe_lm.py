"""Weights of the convolution-and-attention, routed-experts LM family
(LFM2), made by the benchmark from ``--seed`` in the flat layout the
program's ``TransformerLM`` takes with ``layer_types`` beside
``mlp_types``: the two norms of every layer under ``blocks.``; the
convolution layers' ``in_proj``, ``conv_w``, ``out_proj`` under ``sconv.``;
the attention layers' ``wqkv``, ``wo`` and per-head q/k norm scales under
``attn.``; the dense layers' gated MLP under ``dense.``; the expert layers'
router ``gate`` over all ``n_experts``, its float32 ``expert_bias`` and the
routed experts' three matrices stacked over the experts *held here* (in the
order of ``experts_held``) under ``moe.``.  The program and the plain
reference are both handed what is made here.

The start (the configuration file's ``assumed.init``): every matrix and the
convolution's taps normal / sqrt(fan_in), norm scales one, all in the
model's type; the expert bias zero, float32.
"""
import math

import jax
import jax.numpy as jnp

from . import weights


def sizes(m):
    """The widths and counts the leaves are cut from."""
    heads, kv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    types, mlps = list(m["layer_types"]), list(m["mlp_types"])
    return {"e": m["d_model"], "v": m["vocab_size"], "heads": heads,
            "kv": kv, "d": d, "q": heads * d, "qkv": (heads + 2 * kv) * d,
            "f": m["d_ff"], "fe": m["moe_d_ff"], "n": m["n_experts"],
            "held": len(m["experts_held"]) or m["n_experts"],
            "k": m["moe_top_k"], "K": m["short_conv"],
            "layers": m["n_layers"], "attn": types.count("attention"),
            "conv": types.count("conv"), "dense": mlps.count("dense"),
            "moe": mlps.count("moe")}


def leaf_shapes(m):
    """{name: (shape, fan_in, or None for a scale of ones, or 0 for
    zeros)}."""
    s = sizes(m)
    e, f, fe, held = s["e"], s["f"], s["fe"], s["held"]
    na, nc, nd, nm = s["attn"], s["conv"], s["dense"], s["moe"]
    return {
        "embed": ((s["v"], e), e),
        "final_ln_scale": ((e,), None),
        "blocks.ln1_scale": ((s["layers"], e), None),
        "blocks.ln2_scale": ((s["layers"], e), None),
        "attn.wqkv": ((na, e, s["qkv"]), e),
        "attn.wo": ((na, s["q"], e), s["q"]),
        "attn.q_norm_scale": ((na, s["d"]), None),
        "attn.k_norm_scale": ((na, s["d"]), None),
        "sconv.in_proj": ((nc, e, 3 * e), e),
        "sconv.conv_w": ((nc, s["K"], e), s["K"]),
        "sconv.out_proj": ((nc, e, e), e),
        "dense.w_gate": ((nd, e, f), e),
        "dense.w_up": ((nd, e, f), e),
        "dense.w_down": ((nd, f, e), f),
        "moe.gate": ((nm, e, s["n"]), e),
        "moe.expert_bias": ((nm, s["n"]), 0),
        "moe.moe_gate": ((nm, held, e, fe), e),
        "moe.moe_up": ((nm, held, e, fe), e),
        "moe.moe_down": ((nm, held, fe, e), fe),
    }


def param_count(m):
    return sum(math.prod(shape) for shape, _f in leaf_shapes(m).values())


def _leaf(name, spec, key, dtype, index):
    shape, fan_in = spec
    if fan_in == 0:
        return jnp.zeros(shape, jnp.float32)
    return weights._lm_leaf(name, spec, key, dtype, index)


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
