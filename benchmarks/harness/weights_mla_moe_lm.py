"""Weights of the latent-attention, routed-experts LM family (DeepSeek-V2),
made by the benchmark from ``--seed`` in the flat layout the program's
``TransformerLM`` takes with ``attention = "mla"`` and ``mlp_types``: leaves
every layer has under ``blocks.`` (the two norms and the MLA mixer, stacked
over all layers), the dense layers' MLP under ``dense.`` and the expert
layers' under ``moe.`` (each stacked over the layers of its kind; the routed
experts' matrices stacked again over the experts *held here*, in the order of
``experts_held``).  The program and the plain reference are both handed what
is made here.

The start (the configuration file's ``assumed.init``): every matrix normal /
sqrt(fan_in), norm scales one; all in the model's type.
"""
import math

import jax
import jax.numpy as jnp

from . import weights

COMMON = ("ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_norm_scale", "wkv_b",
          "wo")
DENSE = ("w_gate", "w_up", "w_down")
MOE = ("gate", "moe_gate", "moe_up", "moe_down", "shared_gate", "shared_up",
       "shared_down")
OUTER = ("embed", "final_ln_scale", "unembed")


def sizes(m):
    """The widths the leaves are cut from."""
    return {"e": m["d_model"], "f": m["d_ff"], "v": m["vocab_size"],
            "heads": m["n_heads"], "dn": m["qk_nope_head_dim"],
            "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"],
            "r": m["kv_lora_rank"], "fe": m["moe_d_ff"],
            "fs": m["moe_shared_d_ff"], "n": m["n_experts"],
            "held": len(m["experts_held"]) or m["n_experts"],
            "k": m["moe_top_k"], "layers": m["n_layers"],
            "n_dense": m["mlp_types"].count("dense"),
            "n_moe": m["mlp_types"].count("moe")}


def leaf_shapes(m):
    """{name: (shape, fan_in, or None for a scale of ones)}."""
    s = sizes(m)
    e, f, fe, fs, h = s["e"], s["f"], s["fe"], s["fs"], s["heads"]
    lay, ld, lm, held = s["layers"], s["n_dense"], s["n_moe"], s["held"]
    hv = h * s["dv"]
    return {
        "embed": ((s["v"], e), e),
        "final_ln_scale": ((e,), None),
        "unembed": ((e, s["v"]), e),
        "blocks.ln1_scale": ((lay, e), None),
        "blocks.ln2_scale": ((lay, e), None),
        "blocks.wq": ((lay, e, h * (s["dn"] + s["dr"])), e),
        "blocks.wkv_a": ((lay, e, s["r"] + s["dr"]), e),
        "blocks.kv_norm_scale": ((lay, s["r"]), None),
        "blocks.wkv_b": ((lay, s["r"], h * (s["dn"] + s["dv"])), s["r"]),
        "blocks.wo": ((lay, hv, e), hv),
        "dense.w_gate": ((ld, e, f), e),
        "dense.w_up": ((ld, e, f), e),
        "dense.w_down": ((ld, f, e), f),
        "moe.gate": ((lm, e, s["n"]), e),
        "moe.moe_gate": ((lm, held, e, fe), e),
        "moe.moe_up": ((lm, held, e, fe), e),
        "moe.moe_down": ((lm, held, fe, e), fe),
        "moe.shared_gate": ((lm, e, fs), e),
        "moe.shared_up": ((lm, e, fs), e),
        "moe.shared_down": ((lm, fs, e), fs),
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
