"""Weights of the window-and-full-attention, routed-experts LM family
(SmallThinker), made by the benchmark from ``--seed`` in the flat layout the
program's ``TransformerLM`` takes with ``use_moe`` and per-layer attention
settings: every leaf stacked over all layers under ``blocks.`` (the two
norms, the fused ``wqkv`` at its own head width, ``wo``, the router ``gate``
over all ``n_experts``, and the routed experts' three matrices stacked again
over the experts *held here*, in the order of ``experts_held``).  The
program and the plain reference are both handed what is made here.

The start (the configuration file's ``assumed.init``): every matrix normal /
sqrt(fan_in), norm scales one; all in the model's type.
"""
import math

import jax
import jax.numpy as jnp

from . import weights

BLOCK = ("ln1_scale", "ln2_scale", "wqkv", "wo", "gate", "moe_gate",
         "moe_up", "moe_down")
OUTER = ("embed", "final_ln_scale", "unembed")


def sizes(m):
    """The widths the leaves are cut from."""
    heads, kv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"e": m["d_model"], "v": m["vocab_size"], "heads": heads,
            "kv": kv, "d": d, "q": heads * d, "qkv": (heads + 2 * kv) * d,
            "fe": m["moe_d_ff"], "n": m["n_experts"],
            "held": len(m["experts_held"]) or m["n_experts"],
            "k": m["moe_top_k"], "layers": m["n_layers"],
            "windows": list(m["attn_windows"]), "rope": list(m["attn_rope"])}


def leaf_shapes(m):
    """{name: (shape, fan_in, or None for a scale of ones)}."""
    s = sizes(m)
    e, fe, lay, held = s["e"], s["fe"], s["layers"], s["held"]
    return {
        "embed": ((s["v"], e), e),
        "final_ln_scale": ((e,), None),
        "unembed": ((e, s["v"]), e),
        "blocks.ln1_scale": ((lay, e), None),
        "blocks.ln2_scale": ((lay, e), None),
        "blocks.wqkv": ((lay, e, s["qkv"]), e),
        "blocks.wo": ((lay, s["q"], e), s["q"]),
        "blocks.gate": ((lay, e, s["n"]), e),
        "blocks.moe_gate": ((lay, held, e, fe), e),
        "blocks.moe_up": ((lay, held, e, fe), e),
        "blocks.moe_down": ((lay, held, fe, e), fe),
    }


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
