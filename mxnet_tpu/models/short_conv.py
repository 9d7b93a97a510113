"""The gated short-convolution mixer of a hybrid LM (LFM2's ``conv`` layer):
one kind of ``TransformerLM`` layer (``TransformerConfig.layer_types``),
in the attention half's place.

    [b, c, u] = in_proj(h)                          E -> 3 E, in that order
    y         = c * conv1d_depthwise_causal(b * u)  K taps, no bias
    out       = out_proj(y)                         E -> E

No activation, no bias.  The gated convolution is one operation with its
own backward (`ops/pallas/short_conv.py`: the Pallas kernels on a single TPU
chip, the same mathematics in lax elsewhere), float32 inside.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas.short_conv import gated_short_conv

__all__ = ["short_conv_mixer", "short_conv_leaf_shapes", "short_conv_init",
           "IN_PROJ_NAME"]

# checkpoint_name of ``in_proj``'s output [B, T, 3 E]: the mixer's widest
# product, which a rematerialised layer may keep (the backward then re-runs
# the convolution alone)
IN_PROJ_NAME = "sconv_in_proj"


def short_conv_leaf_shapes(cfg):
    """``{leaf: (shape of one layer, fan_in)}`` of a convolution mixer;
    ``conv_w[j]`` is the tap ``K - 1 - j`` steps back."""
    E, K = cfg.d_model, cfg.short_conv
    return {
        "in_proj": ((E, 3 * E), E),
        "conv_w": ((K, E), K),
        "out_proj": ((E, E), E),
    }


def short_conv_init(cfg, rng, n_layers):
    """``n_layers`` stacked mixers: every leaf normal / sqrt(fan_in)."""
    dt = jnp.dtype(cfg.dtype)
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(
            sorted(short_conv_leaf_shapes(cfg).items())):
        out[name] = (jax.random.normal(jax.random.fold_in(rng, i),
                                       (n_layers,) + shape, jnp.float32)
                     / math.sqrt(fan_in)).astype(dt)
    return out


def short_conv_mixer(bp, h):
    """One mixer on the normed input ``h`` [B, T, E] -> [B, T, E]."""
    dt = h.dtype
    with jax.named_scope("sconv.in_proj"):
        bcu = jnp.einsum("bte,ef->btf", h, bp["in_proj"],
                         preferred_element_type=jnp.float32).astype(dt)
        b, c, u = jnp.split(checkpoint_name(bcu, IN_PROJ_NAME), 3, axis=-1)
    y = gated_short_conv(b, c, u, bp["conv_w"])
    with jax.named_scope("sconv.out_proj"):
        return jnp.einsum("bte,ef->btf", y, bp["out_proj"],
                          preferred_element_type=jnp.float32).astype(dt)
