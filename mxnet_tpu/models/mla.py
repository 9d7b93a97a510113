"""Multi-head latent attention (MLA) as DeepSeek-V2 builds it: the attention
half of a ``TransformerLM`` layer with ``TransformerConfig.attention =
"mla"``.  Keys and values come from one low-rank latent a token, and the
positional term lives in a rotary part of its own, decoupled from it:

    q            = h . wq                        E -> H (dn + dr)
    [c, k_pe]    = h . wkv_a                     E -> r + dr
    c            = rms(c) g                      the inner norm, r wide
    [k_nope, v]  = c . wkv_b                     r -> H (dn + dv)
    q_pe, k_pe   = rope(q_pe), rope(k_pe)        one rotary key head for all
    o            = softmax(scale [q_nope, q_pe] . [k_nope, k_pe]^T) v
    out          = o . wo                        H dv -> E

``dn`` / ``dr`` / ``dv`` are ``qk_nope_head_dim`` / ``qk_rope_head_dim`` /
``v_head_dim``, ``r`` is ``kv_lora_rank``; the query is not compressed
(``q_lora_rank`` null).  The scores are ``dn + dr`` wide and the values
``dv``: the flash kernels take the two widths (`ops/pallas/
flash_attention.py`).  The rotary frequencies are YaRN's (:func:`yarn_inv_
freq`), in the rotate-half layout (the first ``dr / 2`` columns of a rotary
part pair with the last); ``scale = (dn + dr) ** -0.5 * mscale ** 2``
(:func:`softmax_scale`).  Cosines, sines and the rotation are float32.
Training path only: serving would keep ``c`` and ``k_pe`` (a latent cache),
which the paged pool does not (ROADMAP R-m2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas import fused_rmsnorm

__all__ = ["mla_mixer", "mla_leaf_shapes", "yarn_inv_freq", "yarn_mscale",
           "yarn_correction_range", "softmax_scale", "rope_tables",
           "rotate_half"]


def mla_leaf_shapes(cfg):
    """``{leaf: (shape of one layer, fan_in or None)}`` of an MLA mixer."""
    E, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    return {
        "wq": ((E, H * (dn + dr)), E),
        "wkv_a": ((E, r + dr), E),
        "kv_norm_scale": ((r,), None),
        "wkv_b": ((r, H * (dn + dv)), r),
        "wo": ((H * dv, E), H * dv),
    }


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


def softmax_scale(cfg):
    """``(dn + dr) ** -0.5`` times the square of YaRN's temperature over
    all dimensions (``rope_mscale_all_dim``; 0: none)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def rope_tables(cfg, T):
    """``(cos, sin)`` [T, dr] float32 for positions 0 .. T - 1, each pair's
    angle in columns ``i`` and ``i + dr / 2``."""
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                        cfg.rope_factor, cfg.rope_orig_len,
                        cfg.rope_beta_fast, cfg.rope_beta_slow)
    angle = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return (jnp.asarray(np.cos(angle) * m, jnp.float32),
            jnp.asarray(np.sin(angle) * m, jnp.float32))


def rotate_half(x, cos, sin):
    """x [B, T, heads, dr] turned by its position's angles, in float32."""
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    turned = jnp.concatenate([-b, a], axis=-1)
    return (xf * cos[None, :, None, :]
            + turned * sin[None, :, None, :]).astype(x.dtype)


def mla_mixer(bp, h, cfg, attend):
    """One mixer on the normed input ``h`` [B, T, E] -> [B, T, E].
    ``attend(q, k, v, scale)`` is the model's causal attention over q, k
    [B, T, H, dn + dr] and v [B, T, H, dv]."""
    B, T, _ = h.shape
    H = cfg.n_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)

    def proj(x, w):
        return jnp.einsum("btf,fg->btg", x, w,
                          preferred_element_type=jnp.float32).astype(h.dtype)

    with jax.named_scope("mla.q"):
        q_nope, q_pe = jnp.split(
            proj(h, bp["wq"]).reshape(B, T, H, dn + dr), [dn], axis=-1)
    with jax.named_scope("mla.kv_a"):
        c, k_pe = jnp.split(proj(h, bp["wkv_a"]), [r], axis=-1)
        c = fused_rmsnorm(c, bp["kv_norm_scale"].astype(c.dtype))
    with jax.named_scope("mla.kv_b"):
        k_nope, v = jnp.split(
            proj(c, bp["wkv_b"]).reshape(B, T, H, dn + dv), [dn], axis=-1)
    with jax.named_scope("mla.rope"):
        cos, sin = rope_tables(cfg, T)
        q_pe = rotate_half(q_pe, cos, sin)
        k_pe = rotate_half(k_pe[:, :, None, :], cos, sin)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        # the one rotary key head, broadcast to the query heads (the
        # backward sums its gradient over them by autodiff)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, T, H, dr))], axis=-1)
    o = attend(q, k, v, softmax_scale(cfg))
    return proj(o.reshape(B, T, H * dv), bp["wo"])
