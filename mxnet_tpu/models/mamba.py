"""The Mamba-1 mixer as Jamba builds it: the state-space half of a hybrid
``TransformerLM`` layer (``TransformerConfig.layer_types``).

    [u, z]    = in_proj(h)                                  E -> 2 Di
    u         = silu(conv1d_depthwise_causal(u) + b_conv)   own last K steps
    [d, B, C] = x_proj(u)                                   Di -> R + N + N
    d, B, C   = rms(d) g_d, rms(B) g_B, rms(C) g_C          three inner norms
    delta     = softplus(dt_proj(d) + b_dt)                 R -> Di
    out       = out_proj(selective_scan(u, delta, -exp(A_log), B, C, D, z))

The recurrence itself is `ops/pallas/selective_scan.py` (the Pallas kernel
on a single TPU chip, the same chunked mathematics in lax elsewhere).
``delta``, ``A``, the state and every ``exp`` are float32 whatever the
model's dtype; ``A_log``, ``D`` and ``dt_bias`` are float32 leaves.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# the three inner norms are 160 and 16 wide: the plain lax form, not the
# kernel the layer norms take
from ..ops.pallas.layers import _rmsnorm_lax as rms
from ..ops.pallas.selective_scan import selective_scan

__all__ = ["mamba_mixer", "mamba_leaf_shapes", "mamba_init", "IN_PROJ_NAME"]

# checkpoint_name of ``in_proj``'s output [B, T, 2 Di]: the widest product
# of the mixer, which a rematerialised layer may keep
IN_PROJ_NAME = "ssm_in_proj"

# leaves kept in float32 whatever the model's dtype (Mamba's convention)
F32_LEAVES = ("A_log", "D", "dt_bias")


def mamba_leaf_shapes(cfg):
    """``{leaf: (shape of one layer, fan_in or None)}`` of a Mamba mixer."""
    E, Di, N, R, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                      cfg.ssm_dt_rank, cfg.ssm_conv)
    return {
        "in_proj": ((E, 2 * Di), E),
        "conv_w": ((K, Di), K),
        "conv_b": ((Di,), K),
        "x_proj": ((Di, R + 2 * N), Di),
        "dt_norm_scale": ((R,), None),
        "b_norm_scale": ((N,), None),
        "c_norm_scale": ((N,), None),
        "dt_proj": ((R, Di), R),
        "dt_bias": ((Di,), None),
        "A_log": ((Di, N), None),
        "D": ((Di,), None),
        "out_proj": ((Di, E), Di),
    }


def mamba_init(cfg, rng, n_layers):
    """Mamba's published start for ``n_layers`` stacked mixers: matrices
    and the convolution's bias normal / sqrt(fan_in), norm scales and ``D``
    one, ``A_log = log(1..N)``, ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.dtype(cfg.dtype)
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(
            sorted(mamba_leaf_shapes(cfg).items())):
        shape = (n_layers,) + shape
        key = jax.random.fold_in(rng, i)
        if name == "A_log":
            leaf = jnp.broadcast_to(
                jnp.log(jnp.arange(1, cfg.ssm_state + 1, dtype=jnp.float32)),
                shape)
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            leaf = step + jnp.log(-jnp.expm1(-step))
        elif fan_in is None:
            leaf = jnp.ones(shape, jnp.float32 if name in F32_LEAVES else dt)
        else:
            leaf = (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dt)
        out[name] = leaf
    return out


def _causal_conv(u, w, b):
    """Depthwise causal convolution over time: channel ``d`` at step ``t``
    sees its own steps ``t - K + 1 .. t`` (zeros before the start).  K
    shifted multiply-adds, which XLA fuses; float32 inside."""
    K, T = w.shape[0], u.shape[1]
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for k in range(K):
        out = out + up[:, k:k + T] * wf[k]
    return out


def mamba_mixer(bp, h, cfg):
    """One mixer on the normed input ``h`` [B, T, E] -> [B, T, E]."""
    dt = h.dtype
    N, R = cfg.ssm_state, cfg.ssm_dt_rank

    def proj(x, w):
        return jnp.einsum("btf,fg->btg", x, w,
                          preferred_element_type=jnp.float32)

    with jax.named_scope("ssm.in_proj"):
        u, z = jnp.split(checkpoint_name(proj(h, bp["in_proj"]).astype(dt),
                                         IN_PROJ_NAME), 2, axis=-1)
    with jax.named_scope("conv"):
        u = jax.nn.silu(_causal_conv(u, bp["conv_w"], bp["conv_b"])
                        ).astype(dt)
    with jax.named_scope("ssm.x_proj"):
        d, B, C = jnp.split(proj(u, bp["x_proj"]).astype(dt), [R, R + N],
                            axis=-1)
    with jax.named_scope("ssm.dt"):
        d = rms(d, bp["dt_norm_scale"], 1e-6)
        B = rms(B, bp["b_norm_scale"], 1e-6)
        C = rms(C, bp["c_norm_scale"], 1e-6)
        delta = jax.nn.softplus(proj(d, bp["dt_proj"])
                                + bp["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(bp["A_log"].astype(jnp.float32))
    with jax.named_scope("scan"):
        y = selective_scan(u, delta, A, B, C, bp["D"], z)
    with jax.named_scope("ssm.out_proj"):
        return proj(y, bp["out_proj"]).astype(dt)
