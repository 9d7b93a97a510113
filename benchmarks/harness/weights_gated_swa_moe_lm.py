"""Weights of the gated window-and-full-attention, routed-experts LM family
(Laguna), made by the benchmark from ``--seed`` in the flat layout the
program's ``TransformerLM`` takes with query heads set by layer
(``attn_heads``), a per-head output gate and ``mlp_types``: the two norms of
every layer under ``blocks.``; the fused-QKV mixer's ``wqkv``, ``wo`` and
``head_gate`` one stack a head count, under ``attn<H>.`` (the layers of
that head count, in order); the dense layers' gated MLP under ``dense.``;
the expert layers' router ``gate`` over all ``n_experts``, the routed
experts' three matrices stacked over the experts *held here* (in the order
of ``experts_held``) and the shared expert's three under ``moe.``.  The
program and the plain reference are both handed what is made here.

The start (the configuration file's ``assumed.init``): every matrix normal /
sqrt(fan_in), norm scales one; all in the model's type.
"""
import math

import jax
import jax.numpy as jnp

from . import weights


def sizes(m):
    """The widths and counts the leaves are cut from."""
    mlps = list(m["mlp_types"])
    return {"e": m["d_model"], "v": m["vocab_size"], "kv": m["n_kv_heads"],
            "d": m["head_dim"], "f": m["d_ff"], "fe": m["moe_d_ff"],
            "fs": m["moe_shared_d_ff"], "n": m["n_experts"],
            "held": len(m["experts_held"]) or m["n_experts"],
            "k": m["moe_top_k"], "layers": m["n_layers"],
            "heads": [h or m["n_heads"] for h in m["attn_heads"]],
            "windows": list(m["attn_windows"]),
            "dense": mlps.count("dense"), "moe": mlps.count("moe")}


def leaf_shapes(m):
    """{name: (shape, fan_in, or None for a scale of ones)}."""
    s = sizes(m)
    e, d, kv, f, fe, fs = s["e"], s["d"], s["kv"], s["f"], s["fe"], s["fs"]
    nd, nm, held = s["dense"], s["moe"], s["held"]
    out = {
        "embed": ((s["v"], e), e),
        "final_ln_scale": ((e,), None),
        "unembed": ((e, s["v"]), e),
        "blocks.ln1_scale": ((s["layers"], e), None),
        "blocks.ln2_scale": ((s["layers"], e), None),
        "dense.w_gate": ((nd, e, f), e),
        "dense.w_up": ((nd, e, f), e),
        "dense.w_down": ((nd, f, e), f),
        "moe.gate": ((nm, e, s["n"]), e),
        "moe.moe_gate": ((nm, held, e, fe), e),
        "moe.moe_up": ((nm, held, e, fe), e),
        "moe.moe_down": ((nm, held, fe, e), fe),
        "moe.shared_gate": ((nm, e, fs), e),
        "moe.shared_up": ((nm, e, fs), e),
        "moe.shared_down": ((nm, fs, e), fs),
    }
    for h in sorted(set(s["heads"])):
        n = s["heads"].count(h)
        out["attn%d.wqkv" % h] = ((n, e, (h + 2 * kv) * d), e)
        out["attn%d.wo" % h] = ((n, h * d, e), h * d)
        out["attn%d.head_gate" % h] = ((n, e, h), e)
    return out


def param_count(m):
    return sum(math.prod(shape) for shape, _f in leaf_shapes(m).values())


def init(m, seed):
    """All leaves in one jitted call."""
    specs = leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    names = sorted(specs)

    def make(key):
        return {n: weights._lm_leaf(n, specs[n], key, dtype, i)
                for i, n in enumerate(names)}

    return jax.jit(make)(weights.key_from_seed(seed))


def init_leaf(m, seed, name):
    """One leaf, the same values ``init`` gives it."""
    specs = leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    index = sorted(specs).index(name)
    fn = jax.jit(lambda key: weights._lm_leaf(name, specs[name], key, dtype,
                                              index))
    return fn(weights.key_from_seed(seed))
