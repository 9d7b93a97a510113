"""The rotary positional term, said once for the mixers that have one: the
latent mixer's decoupled rotary part (`models/mla.py`) and the fused-QKV
mixer's whole heads on the layers whose ``attn_rope`` entry says so
(`models/transformer.py::_qkv`).

Rotate-half layout: column ``i`` of a rotary part pairs with column ``i +
dim / 2``; pair ``i`` of position ``t`` turns by ``t f_i``, ``f_i = base **
(-2 i / dim)``, or YaRN's blend of ``f_i`` and ``f_i / factor`` where
``rope_factor`` > 1 (:func:`yarn_inv_freq`).  Cosines, sines and the
rotation are float32.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

__all__ = ["yarn_inv_freq", "yarn_mscale", "yarn_correction_range",
           "rope_tables", "rotate_half"]


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim, base, orig_len, beta_fast, beta_slow):
    """``(low, high)``: the rotary pairs between which YaRN blends from the
    published frequencies (below ``low``: pairs that turn more than
    ``beta_fast`` times over the original context) to the interpolated ones
    (above ``high``: fewer than ``beta_slow`` turns)."""
    def pair_of(turns):
        return dim * math.log(orig_len / (turns * 2 * math.pi)) / (
            2 * math.log(base))
    return (max(math.floor(pair_of(beta_fast)), 0),
            min(math.ceil(pair_of(beta_slow)), dim - 1))


def yarn_inv_freq(dim, base, factor, orig_len, beta_fast, beta_slow):
    """[dim / 2] float64 inverse frequencies: ``base ** (-2 i / dim)``,
    divided by ``factor`` where the ramp over the correction range is 1."""
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra
    low, high = yarn_correction_range(dim, base, orig_len, beta_fast,
                                      beta_slow)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_tables(cfg, dim, T):
    """``(cos, sin)`` [T, dim] float32 for positions 0 .. T - 1 of a rotary
    part ``dim`` wide, each pair's angle in columns ``i`` and ``i + dim /
    2``, from ``cfg``'s ``rope_*`` keys."""
    inv = yarn_inv_freq(dim, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_orig_len, cfg.rope_beta_fast,
                        cfg.rope_beta_slow)
    angle = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return (jnp.asarray(np.cos(angle) * m, jnp.float32),
            jnp.asarray(np.sin(angle) * m, jnp.float32))


def rotate_half(x, cos, sin):
    """x [B, T, heads, dim] turned by its position's angles, in float32."""
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    turned = jnp.concatenate([-b, a], axis=-1)
    return (xf * cos[None, :, None, :]
            + turned * sin[None, :, None, :]).astype(x.dtype)
