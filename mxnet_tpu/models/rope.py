"""The rotary positional term, said once for the mixers that have one: the
latent mixer's decoupled rotary part (`models/mla.py`) and the fused-QKV
mixer's heads on the layers whose ``attn_rope`` entry says so
(`models/transformer.py::_qkv`).

A term is built from a :class:`RopeSetting`: the model-wide ``rope_*`` keys
(``TransformerConfig.rope``) or a layer's own setting.  Rotate-half layout:
column ``i`` of a rotary part pairs with column ``i + dim / 2``; pair ``i``
of position ``t`` turns by ``t f_i``, ``f_i = base ** (-2 i / dim)``, or
YaRN's blend of ``f_i`` and ``f_i / factor`` where ``factor`` > 1
(:func:`yarn_inv_freq`).  A setting with ``fraction`` < 1 turns the leading
``fraction`` of a head and passes the rest through; its frequencies are
those of a rotary part that wide.  Cosines, sines and the rotation are
float32.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

__all__ = ["RopeSetting", "yarn_inv_freq", "yarn_mscale",
           "yarn_correction_range", "rope_tables", "rotate_half"]


@dataclasses.dataclass(frozen=True)
class RopeSetting:
    """One rotary term.  ``factor`` > 1 is YaRN over ``orig_len`` with the
    blend between ``beta_fast`` and ``beta_slow`` turns; cosines and sines
    carry YaRN's temperature ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)`` (1 without YaRN).  ``fraction``:
    the leading share of a head that turns."""
    theta: float = 10000.0
    factor: float = 1.0
    orig_len: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    fraction: float = 1.0

    def __post_init__(self):
        assert 0 < self.fraction <= 1, "fraction %r" % self.fraction

    def dims(self, head_dim):
        """How many of a head's ``head_dim`` columns turn: an even count."""
        rot = int(head_dim * self.fraction)
        assert rot and rot % 2 == 0, \
            "a rotary part of %d of %d columns" % (rot, head_dim)
        return rot


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


def rope_tables(setting, dim, T):
    """``(cos, sin)`` [T, dim] float32 for positions 0 .. T - 1 of a rotary
    part ``dim`` wide, each pair's angle in columns ``i`` and ``i + dim /
    2``, from ``setting`` (a :class:`RopeSetting`)."""
    s = setting
    inv = yarn_inv_freq(dim, s.theta, s.factor, s.orig_len, s.beta_fast,
                        s.beta_slow)
    angle = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    m = (yarn_mscale(s.factor, s.mscale)
         / yarn_mscale(s.factor, s.mscale_all_dim))
    return (jnp.asarray(np.cos(angle) * m, jnp.float32),
            jnp.asarray(np.sin(angle) * m, jnp.float32))


def rotate_half(x, cos, sin):
    """x [B, T, heads, D] turned by its position's angles, in float32.  Where
    the tables are narrower than ``D`` the leading columns turn and the rest
    pass through untouched."""
    rot = cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate([rotate_half(x[..., :rot], cos, sin),
                                x[..., rot:]], axis=-1)
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    turned = jnp.concatenate([-b, a], axis=-1)
    return (xf * cos[None, :, None, :]
            + turned * sin[None, :, None, :]).astype(x.dtype)
