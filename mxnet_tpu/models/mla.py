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
flash_attention.py`).  The rotary term is `models/rope.py`'s (YaRN's
frequencies, the rotate-half layout); ``scale = (dn + dr) ** -0.5 * mscale
** 2`` (:func:`softmax_scale`).
Training path only: serving would keep ``c`` and ``k_pe`` (a latent cache),
which the paged pool does not (ROADMAP R-m2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.pallas import fused_rmsnorm
from .rope import rope_tables, rotate_half, yarn_mscale

__all__ = ["mla_mixer", "mla_leaf_shapes", "softmax_scale"]


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


def softmax_scale(cfg):
    """``(dn + dr) ** -0.5`` times the square of YaRN's temperature over
    all dimensions (``rope_mscale_all_dim``; 0: none)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


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
        with jax.named_scope("norm"):
            c = fused_rmsnorm(c, bp["kv_norm_scale"].astype(c.dtype))
    with jax.named_scope("mla.kv_b"):
        k_nope, v = jnp.split(
            proj(c, bp["wkv_b"]).reshape(B, T, H, dn + dv), [dn], axis=-1)
    with jax.named_scope("mla.rope"):
        cos, sin = rope_tables(cfg.rope, dr, T)
        q_pe = rotate_half(q_pe, cos, sin)
        k_pe = rotate_half(k_pe[:, :, None, :], cos, sin)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        # the one rotary key head, broadcast to the query heads (the
        # backward sums its gradient over them by autodiff)
        with jax.named_scope("attn.kv_broadcast"):
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (B, T, H, dr))], axis=-1)
    o = attend(q, k, v, softmax_scale(cfg))
    with jax.named_scope("attn.out"):
        return proj(o.reshape(B, T, H * dv), bp["wo"])
