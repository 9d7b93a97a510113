"""Sharding-native Transformer language model — the flagship multi-chip model.

Everything the reference lacked for long-context/distributed (SURVEY.md §2.4,
§5): one model covering data parallel (``dp``), ZeRO-style parameter sharding
(``fsdp``), tensor parallel (``tp`` — Megatron-style column/row splits
expressed *declaratively* as GSPMD shardings, XLA inserts the collectives),
sequence parallel via ring attention (``sp``, `parallel/ring_attention.py`),
routed experts (`parallel/moe.py`: a chip's share of them without dropped
tokens; the capacity dispatch over ``ep``), and a GPipe pipeline variant
(``pp``, `parallel/pipeline.py`).  A layer's attention half is fused-QKV
attention (over the whole causal prefix or a window of it, with a rotary
term or none, by layer), latent attention (`models/mla.py`) or a Mamba
mixer (`models/mamba.py`); its MLP half dense or experts, by layer.

Design notes (TPU-first):
* parameters are a flat ``{name: jax.Array}`` dict; layer stacks use a leading
  ``L`` dim + ``lax.scan`` over blocks (ONE traced block body, remat-friendly)
  — not L separately-traced python layers.  A run of equal layers no longer
  than ``_UNROLLED_RUN`` is UNROLLED at compile time (XLA may overlap and
  fuse across layers); a longer run is a loop of one layer a body, which
  bounds the buffers the schedule holds live at once and the compile time
  (``scan_unroll=True``, the default).  ``scan_unroll=False`` is one layer
  a body whatever the run;
* compute dtype bf16, accumulation f32 (MXU-native);
* causal LM loss is computed from sharded logits; everything is static-shaped.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas import (flash_attention, fused_rmsnorm,
                          fused_softmax_xent)
from ..ops.pallas.flash_attention import SAVED_NAMES as _FLASH_KEPT
from ..ops.pallas.selective_scan import SAVED_NAMES as _SCAN_KEPT
from ..parallel.sharding import ShardingRules, constraint, PartitionSpec as P
from ..parallel.ring_attention import ring_self_attention
from ..parallel.moe import SAVED_NAMES as _MOE_KEPT
from ..parallel.moe import expert_layer, moe_layer, route
from .mamba import IN_PROJ_NAME, mamba_mixer
from .mla import mla_leaf_shapes, mla_mixer
from .rope import rope_tables, rotate_half

__all__ = ["TransformerConfig", "TransformerLM", "make_train_step",
           "default_rules"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_len: int = 2048
    dtype: str = "bfloat16"
    use_moe: bool = False
    n_experts: int = 8
    moe_aux_weight: float = 0.01
    remat: bool = True
    # True: a run of equal layers no longer than ``_UNROLLED_RUN`` is
    # unrolled whole (one traced body, unrolled execution: XLA may overlap
    # and fuse across layers), a longer one loops a layer a body
    # (``_layers_a_body``).  False: one layer a body whatever the run.
    scan_unroll: bool = True
    # Small attention problems use plain dense attention (the scores
    # materialize); bigger ones take flash so memory stays O(T).  The
    # gate is the f32 score-tensor size B*H*T^2*4 bytes — gating on T
    # alone would let large batches OOM; 0 sends every shape to flash.
    # Not re-measured since PR 27; ROADMAP S1 (c).
    dense_attn_max_score_mb: int = 768
    # Key/value heads shared by groups of query heads (multi-query: 1).
    # 0 means as many as ``n_heads``.  Training path only: the paged pool
    # and the MXKV blob still take equal head counts (ROADMAP R-m2).
    n_kv_heads: int = 0
    # "gelu": down(gelu(up(h))); "swiglu": down(silu(gate(h)) * up(h));
    # "reglu": down(relu(gate(h)) * up(h))
    mlp: str = "gelu"
    # logits = x . embed^T, no ``unembed`` leaf
    tie_embeddings: bool = False
    # One entry a layer, "attention" or "mamba" (a Mamba-1 mixer,
    # `models/mamba.py`, in the attention half's place); () is attention
    # everywhere.  Leaves every layer has stay under ``blocks.``; the
    # mixers' own stack under ``attn.`` and ``ssm.``.
    layer_types: tuple = ()
    ssm_expand: int = 2
    ssm_state: int = 16
    ssm_dt_rank: int = 0                  # 0: ceil(d_model / 16)
    ssm_conv: int = 4
    # "mha": the fused-QKV attention; "mla": latent attention
    # (`models/mla.py`) with the widths below and no ``wqkv``.  Training
    # path only: the paged pool keeps no latent (ROADMAP R-m2).
    attention: str = "mha"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rotary term (`models/rope.py`) of the ``mla`` mixer's rotary part and
    # of the fused-QKV mixer's layers that ``attn_rope`` names;
    # ``rope_factor`` > 1 is YaRN
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_orig_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # One entry a layer, "dense" or "moe": which layers' MLP half is routed
    # experts (`parallel/moe.py::expert_layer`); () is dense everywhere, or
    # with ``use_moe`` experts everywhere.  With it the dense layers'
    # matrices stack under ``dense.`` and the expert layers' under ``moe.``.
    # An expert has the form ``mlp`` names at width ``moe_d_ff`` (0:
    # ``d_ff``); ``moe_shared_d_ff`` > 0 adds an always-on expert of that
    # width beside the routed ones.  The router is over ``n_experts``;
    # ``experts_held`` names the ones whose matrices this model holds (():
    # all): a chip's share of an expert-parallel layer, whose result is
    # those experts' part alone.
    mlp_types: tuple = ()
    moe_top_k: int = 1
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_renormalize: bool = True
    experts_held: tuple = ()
    # True: an expert layer's router reads the layer's input, before the
    # first norm and before attention (the experts are known while
    # attention still runs); False: the rows it dispatches, ``norm2``'s.
    moe_router_pre_attention: bool = False
    # Width of a head of the fused-QKV mixer; 0: ``d_model // n_heads``.
    # With it ``wqkv`` is [E, (n_heads + 2 kv_heads) head_dim] and ``wo``
    # [n_heads head_dim, E].
    head_dim: int = 0
    # The fused-QKV mixer's attention setting, one entry a layer (() is 0
    # everywhere): ``attn_windows`` the window ``W`` (query t sees the keys
    # ``0 <= t - j < W``; 0: the whole causal prefix), ``attn_rope`` 1
    # where q and k are rotated (rotate-half over the whole head, positions
    # 0 .. T - 1).  Both are static: a run of equal layers is cut where
    # either changes.  Training path only (ROADMAP R-m4).
    attn_windows: tuple = ()
    attn_rope: tuple = ()

    def __post_init__(self):
        # a configuration read from JSON brings a list
        for key in ("layer_types", "mlp_types", "experts_held",
                    "attn_windows", "attn_rope"):
            object.__setattr__(self, key, tuple(getattr(self, key)))
        if not self.ssm_dt_rank:
            object.__setattr__(self, "ssm_dt_rank", -(-self.d_model // 16))
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        for key in ("attn_windows", "attn_rope"):
            per_layer = getattr(self, key)
            if not per_layer:
                continue
            assert len(per_layer) == self.n_layers, \
                "%s names %d layers, n_layers is %d" % (
                    key, len(per_layer), self.n_layers)
            assert all(int(v) == v and v >= 0 for v in per_layer), key
            assert self.attention == "mha" or not any(per_layer), \
                "%s beside latent attention: its rotary part and its " \
                "scores are `models/mla.py`'s own" % key
            assert not any(v and kind == "mamba" for v, kind in
                           zip(per_layer, self.layer_types)), \
                "%s names a Mamba layer: a window or a rotary term is " \
                "an attention layer's" % key
        if self.layer_types:
            assert len(self.layer_types) == self.n_layers, \
                "layer_types names %d layers, n_layers is %d" % (
                    len(self.layer_types), self.n_layers)
            assert set(self.layer_types) <= {"attention", "mamba"}
            assert not self.has_experts and self.attention == "mha", \
                "layer_types beside experts or latent attention: the " \
                "runs of one mixer kind and of one MLP kind are not cut " \
                "together yet"
        if self.mlp_types:
            assert len(self.mlp_types) == self.n_layers, \
                "mlp_types names %d layers, n_layers is %d" % (
                    len(self.mlp_types), self.n_layers)
            assert set(self.mlp_types) <= {"dense", "moe"}
        if self.has_experts:
            assert 1 <= self.moe_top_k <= self.n_experts
            assert all(0 <= e < self.n_experts for e in self.experts_held)
        assert self.attention in ("mha", "mla")
        assert self.mlp in _ACT
        assert self.n_heads % self.kv_heads == 0
        assert self.has_experts or not self.moe_router_pre_attention, \
            "moe_router_pre_attention without an expert layer"

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def d_inner(self):
        return self.ssm_expand * self.d_model

    @property
    def has_experts(self):
        return self.use_moe or "moe" in self.mlp_types

    @property
    def n_held(self):
        return len(self.experts_held) or self.n_experts

    def layer_keys(self):
        """One key a layer, ``(mixer kind, (window, rotary), MLP kind)``:
        what the layers of one scanned run have in common."""
        L = self.n_layers
        mixers = self.layer_types or ("attention",) * L
        mlps = self.mlp_types or ("moe" if self.use_moe else "dense",) * L
        windows = self.attn_windows or (0,) * L
        ropes = self.attn_rope or (0,) * L
        return [(mixers[i], (int(windows[i]), bool(ropes[i])), mlps[i])
                for i in range(L)]

    def layer_runs(self):
        """``[(key, lo, hi, own)]``: maximal runs of layers of one key
        (``layer_keys``), as a range over all layers and, in ``own``, over
        the layers of the run's mixer kind and of its MLP kind (the index
        into the ``attn.`` / ``ssm.`` and ``dense.`` / ``moe.`` stacks)."""
        return _runs(self.layer_keys())


def _runs(keys):
    """Maximal runs of equal entries of ``keys`` (see ``layer_runs``)."""
    runs, seen = [], {}
    for i, key in enumerate(keys):
        mixer, _setting, mlp = key
        if runs and runs[-1][0] == key:
            runs[-1][2] = i + 1
        else:
            runs.append([key, i, i + 1,
                         {kind: seen.get(kind, 0) for kind in (mixer, mlp)}])
        for kind in (mixer, mlp):
            seen[kind] = seen.get(kind, 0) + 1
    return [(key, lo, hi, {kind: (at, at + hi - lo)
                           for kind, at in own.items()})
            for key, lo, hi, own in runs]


def default_rules() -> ShardingRules:
    """Megatron/FSDP layout: attention qkv + MLP-up are column-parallel (tp on
    the output feature), proj + MLP-down row-parallel (tp on the input
    feature); fsdp shards the other big dim; MoE experts shard on ep."""
    return ShardingRules([
        (r"embed",        P("tp", "fsdp")),
        (r".*wqkv",       P(None, "fsdp", "tp")),
        (r".*wq",         P(None, "fsdp", "tp")),
        (r".*wkv_a",      P(None, "fsdp", None)),
        (r".*wkv_b",      P(None, None, "tp")),
        (r".*wo",         P(None, "tp", "fsdp")),
        (r".*(w|shared)_up",   P(None, "fsdp", "tp")),
        (r".*(w|shared)_gate", P(None, "fsdp", "tp")),
        (r".*(w|shared)_down", P(None, "tp", "fsdp")),
        (r".*moe_up",     P(None, "ep", "fsdp", None)),
        (r".*moe_gate",   P(None, "ep", "fsdp", None)),
        (r".*moe_down",   P(None, "ep", None, "fsdp")),
        (r".*gate",       P(None, "fsdp", None)),
        (r"unembed",      P("fsdp", "tp")),
        (r".*",           P()),
    ])


def _dense_self_attention(q, k, v, causal=True, scale=None, window=None):
    """Plain materialized attention for short sequences, one fused QK^T ->
    softmax -> PV chain; memory is O(T^2) so the caller gates it by
    ``dense_attn_max_score_mb`` (not re-measured against the flash kernel
    since PR 27; ROADMAP S1 (c)).  ``window``: the flash kernels' band,
    ``0 <= t - j < window``."""
    B, T, H, D = q.shape
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(D) if scale is None else s * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((T, T), bool), -int(window))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.transpose(0, 2, 1, 3)


# What a rematerialised layer (``remat=True``) keeps of its forward, by
# ``checkpoint_name``: the results whose second forward costs most a byte
# kept (PERF.md §6, PR 31, PR 33) -- the selective scan's output and chunk
# boundaries, flash attention's ``o`` and ``lse``, the Mamba ``in_proj``'s
# output, ``wqkv``'s output split into heads, every mixer's output before
# the residual add and the routed experts' down product, the rows the
# combine's backward reads (PR 37).  Everything else (the norms, the
# convolution, the MLP up to ``w_down``, the experts up to theirs) the
# backward re-makes.  A name no layer of a model produces costs nothing.
MIXER_OUT = "mixer_out"
QKV_NAME = "attn_qkv"
KEPT = (_SCAN_KEPT + _FLASH_KEPT + (IN_PROJ_NAME, QKV_NAME, MIXER_OUT)
        + _MOE_KEPT)

# the activation ``TransformerConfig.mlp`` names: of ``up(h)`` in the first
# form, of ``gate(h)`` in the gated ones
_ACT = {"gelu": jax.nn.gelu, "swiglu": jax.nn.silu, "reglu": jax.nn.relu}

# The longest run of equal layers the layer scan unrolls whole
# (``scan_unroll=True``); a longer run is a loop of one layer a body.
# Unrolled, the schedule holds a ``[B, T, d_ff]`` buffer a layer live at
# once (19 of 24 at the dense LM's peak, 99 % of the chip); looped, the kept
# values travel through stacked buffers.  On the v5e (PERF.md §6, PR 33):
# one run of 24 at 4 x 2048 x 2048 reads 13,105 tokens/s unrolled whole and
# 13,471 / 13,080 / 13,023 / 12,915 / 12,790 / 12,021 / 11,513 at 1 / 2 / 3 /
# 4 / 6 / 8 / 12 layers a body; runs of 7, 1, 6 (a hybrid at 1 x 4096 x
# 2560) read 10,898 whole, 10,423 at 1 and 9,634 at 2.  Whole or one: a body
# of a few layers loses to both.  Nothing between 7 and 24 is measured.
_UNROLLED_RUN = 8


def _layers_a_body(n, unrolled=True):
    """How many of a run's ``n`` equal layers one body of the layer loop
    holds: all of a run up to ``_UNROLLED_RUN`` long, else one."""
    return n if unrolled and n <= _UNROLLED_RUN else 1


class TransformerLM:
    """Decoder-only LM.  Methods are pure functions over a flat param dict."""

    def __init__(self, config: TransformerConfig):
        self.cfg = config

    # -- init ----------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.cfg
        L, E, F = cfg.n_layers, cfg.d_model, cfg.d_ff
        HD = cfg.n_heads * cfg.head_dim
        QKV = HD + 2 * cfg.kv_heads * cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        keys = jax.random.split(rng, 8)
        # with layer_types the mixers' leaves stack over their own layers
        n_attn = cfg.layer_types.count("attention") if cfg.layer_types else L
        attn = "attn." if cfg.layer_types else "blocks."

        def norm(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dt)

        p = {
            "embed": norm(keys[0], (cfg.vocab_size, E), E),
            "blocks.ln1_scale": jnp.ones((L, E), dt),
            "blocks.ln2_scale": jnp.ones((L, E), dt),
            "final_ln_scale": jnp.ones((E,), dt),
        }
        if cfg.attention == "mla":
            for i, (k, (shape, fan_in)) in enumerate(
                    sorted(mla_leaf_shapes(cfg).items())):
                p["blocks." + k] = (
                    jnp.ones((L,) + shape, dt) if fan_in is None else
                    norm(jax.random.fold_in(keys[1], i), (L,) + shape,
                         fan_in))
        else:
            p[attn + "wqkv"] = norm(keys[1], (n_attn, E, QKV), E)
            p[attn + "wo"] = norm(keys[2], (n_attn, HD, E), HD)
        if not cfg.tie_embeddings:
            p["unembed"] = norm(keys[3], (E, cfg.vocab_size), E)
        if cfg.layer_types:
            from .mamba import mamba_init
            for k, v in mamba_init(cfg, keys[7],
                                   L - n_attn).items():
                p["ssm." + k] = v
        # the MLP halves: one stack under ``blocks.``, or with ``mlp_types``
        # the dense layers' under ``dense.`` and the expert layers' under
        # ``moe.``
        gated = cfg.mlp != "gelu"
        n_moe = (cfg.mlp_types.count("moe") if cfg.mlp_types
                 else L * cfg.use_moe)
        dense, moe = (("dense.", "moe.") if cfg.mlp_types
                      else ("blocks.", "blocks."))
        if n_moe < L:
            Ld = L - n_moe
            if gated:
                p[dense + "w_gate"] = norm(keys[4], (Ld, E, F), E)
            p[dense + "w_up"] = norm(keys[5], (Ld, E, F), E)
            p[dense + "w_down"] = norm(keys[6], (Ld, F, E), F)
        if n_moe:
            n, held = cfg.n_experts, cfg.n_held
            Fe, Fs = cfg.moe_d_ff or F, cfg.moe_shared_d_ff
            p[moe + "gate"] = norm(keys[4], (n_moe, E, n), E)
            p[moe + "moe_up"] = norm(keys[5], (n_moe, held, E, Fe), E)
            p[moe + "moe_down"] = norm(keys[6], (n_moe, held, Fe, E), Fe)
            own = [jax.random.fold_in(keys[7], 100 + i) for i in range(4)]
            if gated:
                p[moe + "moe_gate"] = norm(own[0], (n_moe, held, E, Fe), E)
            if Fs:
                p[moe + "shared_up"] = norm(own[1], (n_moe, E, Fs), E)
                p[moe + "shared_down"] = norm(own[2], (n_moe, Fs, E), Fs)
                if gated:
                    p[moe + "shared_gate"] = norm(own[3], (n_moe, E, Fs), E)
        return p

    # -- forward -------------------------------------------------------
    def _rmsnorm(self, x, scale):
        return fused_rmsnorm(x, scale.astype(x.dtype))

    def _block(self, bp, x, mixer, scope="attn"):
        """The one layer body of training, prefill and decode: norm ->
        ``mixer(bp, h)`` -> residual -> MLP.  Returns ``(x, aux, state)``,
        ``state`` being whatever the mixer hands back beside its output.
        Where the configuration places an expert layer's router before
        attention, it reads the layer's input here, ahead of the norm, and
        the MLP half is handed what it chose."""
        routing = None
        with jax.named_scope(scope):
            if self.cfg.moe_router_pre_attention and "gate" in bp:
                routing = self._route(bp, x)
            with jax.named_scope("norm"):
                h = self._rmsnorm(x, bp["ln1_scale"])
            o, state = mixer(bp, h)
            with jax.named_scope("residual"):
                x = x + constraint(checkpoint_name(o, MIXER_OUT),
                                   "dp", "sp", None)
        with jax.named_scope("mlp"):
            x, aux = self._mlp_half(bp, x, routing)
        return x, aux, state

    def _route(self, bp, x):
        """An expert layer's router on ``x`` [B, T, E], run apart from the
        rows the layer dispatches: `parallel/moe.py::route`'s result."""
        from .. import telemetry as _telemetry
        cfg = self.cfg
        B, T, E = x.shape
        _telemetry.registry().counter("moe.router.pre_attention").inc()
        with jax.named_scope("moe.route"):
            return route(x.reshape(B * T, E), bp["gate"], cfg.moe_top_k,
                         cfg.moe_renormalize, (B, T))

    def _qkv(self, bp, h, rope=False):
        """The fused projection of ``h`` [B, T, E], split into heads:
        q [B, T, H, D] and k, v [B, T, KV, D]; with ``rope`` q and k turned
        by their positions 0 .. T - 1.  Each is named (``QKV_NAME``) heads
        first, [B, heads, T, D], as the flash kernels take it, q and k
        after the rotation: a rematerialised layer that keeps the name
        hands the backward kernels the stored value as it lies (PERF.md §6,
        PR 33)."""
        cfg = self.cfg
        B, T, _ = h.shape
        H, D, KV = cfg.n_heads, cfg.head_dim, cfg.kv_heads
        with jax.named_scope("attn.qkv"):
            qkv = jnp.einsum("bte,ef->btf", h, bp["wqkv"],
                             preferred_element_type=jnp.float32
                             ).astype(h.dtype)
            qkv = constraint(qkv, "dp", "sp", "tp")

        tables = rope_tables(cfg, D, T) if rope else None

        def heads(x, n, turn=False):
            x = x.reshape(B, T, n, D)
            if turn:
                with jax.named_scope("attn.rope"):
                    x = rotate_half(x, *tables)
            x = x.transpose(0, 2, 1, 3)
            return checkpoint_name(x, QKV_NAME).transpose(0, 2, 1, 3)

        with jax.named_scope("attn.qkv"):
            q, k, v = jnp.split(qkv, [H * D, (H + KV) * D], axis=-1)
            return heads(q, H, rope), heads(k, KV, rope), heads(v, KV)

    def _attn_out(self, bp, attn):
        """The output projection of ``attn`` [B, T, H, D]."""
        B, T = attn.shape[:2]
        with jax.named_scope("attn.out"):
            return jnp.einsum("btf,fe->bte", attn.reshape(B, T, -1),
                              bp["wo"], preferred_element_type=jnp.float32
                              ).astype(attn.dtype)

    def _self_attention(self, bp, h, use_ring=False, window=0, rope=False):
        """The mixer of training and prefill: causal self-attention over
        ``h``, over a ``window`` of it where that is not 0, q and k rotated
        with ``rope``; its state is the layer's ``(k, v)``, for the page
        write."""
        cfg = self.cfg
        B, T, _ = h.shape
        H, KV = cfg.n_heads, cfg.kv_heads
        q, k, v = self._qkv(bp, h, rope)
        kh, vh = k, v
        if KV != H:
            # Shared key/value heads are broadcast to their query heads
            # before attention (the backward sums dk, dv over the group by
            # autodiff), not indexed ``h // group`` inside the kernels: the
            # three flash kernels stay as the equal-head models run them,
            # at the cost of K and V read once a query head, which the
            # kernels' grid does anyway.
            with jax.named_scope("attn.kv_broadcast"):
                kh = jnp.repeat(k, H // KV, axis=2)
                vh = jnp.repeat(v, H // KV, axis=2)
        return self._attn_out(bp, self._attend(q, kh, vh, None, use_ring,
                                               window or None)), (k, v)

    def _attend(self, q, k, v, scale=None, use_ring=False, window=None):
        """Causal attention of q, k [B, T, H, D] and v [B, T, H, Dv] by
        the implementation the shape and the mesh call for; ``window``:
        over the keys ``0 <= t - j < window`` alone."""
        B, T, H, _ = q.shape
        with jax.named_scope("attn.core"):
            if use_ring:
                assert window is None, \
                    "ring attention over sp with a window: not built (a " \
                    "shard would pass on only the blocks its band reaches)"
                return ring_self_attention(q, k, v, causal=True)
            if B * H * T * T * 4 / 1e6 <= self.cfg.dense_attn_max_score_mb:
                return _dense_self_attention(q, k, v, causal=True,
                                             scale=scale, window=window)
            return flash_attention(q, k, v, causal=True, scale=scale,
                                   window=window)

    def _ssm(self, bp, h):
        """The mixer of a state-space layer (`models/mamba.py`)."""
        return mamba_mixer(bp, h, self.cfg), None

    def _mla(self, bp, h):
        """The mixer of a latent-attention layer (`models/mla.py`)."""
        return mla_mixer(bp, h, self.cfg, self._attend), None

    def _mlp(self, h, w_up, w_down, w_gate):
        """``down(gelu(up(h)))``, or with ``w_gate`` ``down(act(gate(h)) *
        up(h))``, ``act`` the gated form's that ``mlp`` names: a dense
        layer's MLP and an expert layer's shared expert."""
        with jax.named_scope("mlp.up"):
            up = jnp.einsum("bte,ef->btf", h, w_up,
                            preferred_element_type=jnp.float32)
            if w_gate is not None:
                gate = jnp.einsum("bte,ef->btf", h, w_gate,
                                  preferred_element_type=jnp.float32)
        with jax.named_scope("mlp.act"):
            if w_gate is not None:
                up = _ACT[self.cfg.mlp](gate) * up
            else:
                up = jax.nn.gelu(up)
            up = constraint(up.astype(h.dtype), "dp", "sp", "tp")
        with jax.named_scope("mlp.down"):
            return jnp.einsum("btf,fe->bte", up, w_down,
                              preferred_element_type=jnp.float32
                              ).astype(h.dtype)

    def _experts(self, bp, h, routing=None):
        """An expert layer's MLP on the normed ``h``: the routed experts
        held here (under a mesh with an ``ep`` axis still the capacity
        dispatch over all of them) plus the shared expert.  ``routing``:
        what a router placed before attention chose (None: the router reads
        ``h``).  Returns ``(ff, [balance term, pairs that landed on held
        experts])``."""
        cfg = self.cfg
        from ..parallel.mesh import current_mesh
        mesh = current_mesh()
        if mesh is not None and mesh.size("ep") > 1:
            assert cfg.mlp == "gelu" and not cfg.experts_held, \
                "the capacity dispatch over ep: ungated experts, all held"
            assert routing is None, \
                "the capacity dispatch over ep with a pre-attention " \
                "router: its router reads the rows it dispatches"
            ff, aux = moe_layer(h, bp["gate"], bp["moe_up"], bp["moe_down"],
                                top_k=cfg.moe_top_k,
                                renormalize=cfg.moe_renormalize,
                                act=jax.nn.gelu)
            held = jnp.float32(0.0)
        else:
            ff, aux, held = expert_layer(
                h, bp["gate"], bp["moe_up"], bp["moe_down"],
                bp.get("moe_gate"), top_k=cfg.moe_top_k,
                experts_held=cfg.experts_held or None,
                renormalize=cfg.moe_renormalize, act=_ACT[cfg.mlp],
                routing=routing)
        if "shared_up" in bp:
            with jax.named_scope("moe.shared"):
                ff = ff + self._mlp(h, bp["shared_up"], bp["shared_down"],
                                    bp.get("shared_gate"))
        return ff, jnp.stack([aux, held])

    def _mlp_half(self, bp, x, routing=None):
        with jax.named_scope("norm"):
            h = self._rmsnorm(x, bp["ln2_scale"])
        if "gate" in bp:                    # the router: an expert layer
            ff, aux = self._experts(bp, h, routing)
        else:
            ff = self._mlp(h, bp["w_up"], bp["w_down"],
                           bp["w_gate"] if "w_gate" in bp else None)
            aux = jnp.float32(0.0)
        with jax.named_scope("residual"):
            return x + constraint(ff, "dp", "sp", None), aux

    # -- generative decode (paged KV cache) ----------------------------
    #
    # Layout: k_pages / v_pages are [L, P, page_size, H, D] in the model
    # dtype.  Page 0 is the reserved GARBAGE page: writes from prompt
    # padding and inactive decode slots are routed there unconditionally,
    # so neither function ever branches on validity — the attention mask
    # (position <= length) is the only consumer-side filter, and stale
    # garbage never leaks into logits.  Per-sequence page tables are
    # [M] int32 (M = max pages per sequence) padded with 0; position t of
    # a sequence lives at flat slot page_table[t // ps] * ps + t % ps.
    # The allocator/scheduler around these functions lives in
    # mxnet_tpu/generation.py (docs/GENERATIVE.md).

    def _refuse_serving(self):
        """The paged decode path knows one block: fused-QKV attention with
        equal head counts in every layer, a dense GELU MLP, an untied head."""
        cfg = self.cfg
        if cfg.has_experts:
            raise NotImplementedError(
                "paged decode does not support expert layers yet: a decode "
                "step's few tokens would go through the sorted dispatch "
                "(ROADMAP R-m3)")
        if cfg.attention == "mla":
            raise NotImplementedError(
                "paged decode does not support latent attention yet: the "
                "pool would keep the latent and the rotary key, not K and "
                "V (ROADMAP R-m2)")
        if cfg.layer_types:
            raise NotImplementedError(
                "paged decode does not support layer_types yet: a "
                "state-space layer needs its state kept beside the KV "
                "pages (ROADMAP R-m5)")
        if any(cfg.attn_windows) or any(cfg.attn_rope):
            raise NotImplementedError(
                "paged decode does not support per-layer windows or a "
                "rotary term yet: the pool gives every layer every page "
                "and a decode step knows no position (ROADMAP R-m4)")
        if (cfg.kv_heads != cfg.n_heads or cfg.mlp != "gelu"
                or cfg.tie_embeddings):
            raise NotImplementedError(
                "paged decode does not support n_kv_heads < n_heads, a "
                "gated MLP or a tied head yet (ROADMAP R-m2)")

    def init_kv_pages(self, num_pages, page_size):
        """Allocate zeroed paged KV storage: ([L,P,ps,H,D], same) pair."""
        cfg = self.cfg
        if (cfg.layer_types or cfg.kv_heads != cfg.n_heads
                or cfg.attention == "mla" or any(cfg.attn_windows)
                or any(cfg.attn_rope)):
            self._refuse_serving()
        shape = (cfg.n_layers, num_pages, page_size, cfg.n_heads,
                 cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def prefill(self, params, k_pages, v_pages, tokens, length, page_table):
        """Run the prompt through the model, writing per-layer K/V into the
        paged cache and returning next-token logits.

        tokens: [1, Tpad] int32 (prompt left-aligned, padded to a shape
        bucket); length: scalar int32, true prompt length (traced — one
        compile per Tpad bucket, not per length); page_table: [M] int32,
        pages backing positions 0..length-1.  Returns
        (k_pages, v_pages, logits [V] f32) where logits are taken at
        position length-1 (the next-token distribution — TTFT comes from
        argmax of this, no decode step needed for the first token).
        """
        cfg = self.cfg
        self._refuse_serving()
        ps = k_pages.shape[2]
        Tpad = tokens.shape[1]
        x = params["embed"][tokens]
        block_names = [k for k in params if k.startswith("blocks.")]
        stacked = {k.split(".", 1)[1]: params[k] for k in block_names}

        t = jnp.arange(Tpad)
        dest = jnp.where(t < length, page_table[t // ps] * ps + t % ps,
                         t % ps)

        def write(pages_l, kv):
            return (pages_l.reshape(-1, *kv.shape[1:])
                    .at[dest].set(kv).reshape(pages_l.shape))

        def body(x, xs):
            bp, kp, vp = xs
            x, _aux, (k, v) = self._block(bp, x, self._self_attention)
            return x, (write(kp, k[0]), write(vp, v[0]))

        x, (k_pages, v_pages) = lax.scan(body, x, (stacked, k_pages, v_pages))
        x = self._rmsnorm(x, params["final_ln_scale"])
        last = lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                        keepdims=False)
        logits = jnp.einsum("e,ev->v", last, params["unembed"],
                            preferred_element_type=jnp.float32)
        return k_pages, v_pages, logits

    def decode_step(self, params, k_pages, v_pages, tokens, page_tables,
                    lens, active):
        """One autoregressive step for a batch of decode slots.

        tokens: [S] int32, the token each slot is appending; page_tables:
        [S, M] int32; lens: [S] int32, sequence length BEFORE this token
        (the token is written at position ``lens`` and attends positions
        0..lens); active: [S] bool, writes from inactive slots go to the
        garbage page.  Returns (k_pages, v_pages, logits [S, V] f32) —
        logits for the NEXT token of each slot.  All shapes are static per
        slot-count bucket, so join/leave churn never recompiles.
        """
        cfg = self.cfg
        self._refuse_serving()
        H, D = cfg.n_heads, cfg.head_dim
        S = tokens.shape[0]
        ps = k_pages.shape[2]
        x = params["embed"][tokens][:, None, :]            # [S, 1, E]
        block_names = [k for k in params if k.startswith("blocks.")]
        stacked = {k.split(".", 1)[1]: params[k] for k in block_names}

        cur_page = jnp.take_along_axis(page_tables, (lens // ps)[:, None],
                                       axis=1)[:, 0]
        dest = jnp.where(active, cur_page, 0) * ps + lens % ps  # [S]
        span = page_tables.shape[1] * ps
        attn_mask = jnp.arange(span)[None, :] <= lens[:, None]  # [S, span]

        def paged_attention(kp, vp, bp, h):
            """The mixer of decode: the token's k, v written at ``dest``,
            then attention over the slot's pages up to its length."""
            q, k, v = self._qkv(bp, h)                         # [S, 1, H, D]
            kp = kp.reshape(-1, H, D).at[dest].set(k[:, 0]).reshape(kp.shape)
            vp = vp.reshape(-1, H, D).at[dest].set(v[:, 0]).reshape(vp.shape)
            kg = kp[page_tables].reshape(S, span, H, D)
            vg = vp[page_tables].reshape(S, span, H, D)
            s = jnp.einsum("shd,skhd->shk", q[:, 0], kg,
                           preferred_element_type=jnp.float32) / math.sqrt(D)
            s = jnp.where(attn_mask[:, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(h.dtype)
            attn = jnp.einsum("shk,skhd->shd", p, vg,
                              preferred_element_type=jnp.float32
                              ).astype(h.dtype)
            return self._attn_out(bp, attn[:, None]), (kp, vp)

        def body(x, xs):
            bp, kp, vp = xs
            x, _aux, pages = self._block(
                bp, x, functools.partial(paged_attention, kp, vp))
            return x, pages

        x, (k_pages, v_pages) = lax.scan(body, x, (stacked, k_pages, v_pages))
        x = self._rmsnorm(x, params["final_ln_scale"])
        logits = jnp.einsum("se,ev->sv", x[:, 0], params["unembed"],
                            preferred_element_type=jnp.float32)
        return k_pages, v_pages, logits

    def _trunk(self, params, tokens):
        """tokens [B, T] -> the last layer's output [B, T, E] and what the
        MLP halves handed back, summed over the layers: 0.0, or in a model
        with expert layers ``[balance term, pairs on held experts]``."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
            x = constraint(x, "dp", "sp", None)

        block_names = [k for k in params if k.startswith("blocks.")]
        stacked = {k.split(".", 1)[1]: params[k] for k in block_names}
        # ring attention contains shard_map, which composes under scan/jit
        from .. import telemetry as _telemetry
        from ..parallel.mesh import current_mesh
        mesh = current_mesh()
        use_ring = mesh is not None and mesh.size("sp") > 1
        counter = _telemetry.registry().counter

        @functools.lru_cache(maxsize=None)
        def body_of(mixer, setting):
            """The scanned body of a run of layers of one mixer kind and
            one attention setting: one function however many runs share
            it, so that they are traced once."""
            if mixer == "mamba":
                mix, scope = self._ssm, "ssm"
            elif cfg.attention == "mla":
                assert not use_ring, "latent attention over sp: not built"
                mix, scope = self._mla, "attn"
            else:
                window, rope = setting
                mix, scope = functools.partial(
                    self._self_attention, use_ring=use_ring, window=window,
                    rope=rope), "attn"

            def body(carry, bp):
                x, aux = carry
                x, a, _ = self._block(bp, x, mix, scope=scope)
                return (x, aux + a), None
            return body

        def layers(carry, run_body, run, kind="attention"):
            """A run of layers of one kind, scanned over ``run``, their
            slice of the stacked leaves, ``_layers_a_body`` layers a loop
            body."""
            if cfg.remat:
                run_body = jax.checkpoint(
                    run_body, policy=jax.checkpoint_policies
                    .save_only_these_names(*KEPT))
            n = len(run["ln1_scale"])
            unroll = _layers_a_body(n, cfg.scan_unroll)
            counter("lm.layers.%s.%dx%d" % (kind, n, unroll)).inc()
            return lax.scan(run_body, carry, run, unroll=unroll)[0]

        carry = (x, jnp.zeros((2,), jnp.float32) if cfg.has_experts
                 else jnp.float32(0.0))
        # one scan a run of equal layers, over that run's slice of the
        # common stack and of its mixer's and its MLP kind's own
        own = {kind: {k.split(".", 1)[1]: v for k, v in params.items()
                      if k.startswith(prefix)}
               for kind, prefix in (("attention", "attn."),
                                    ("mamba", "ssm."),
                                    ("dense", "dense."),
                                    ("moe", "moe."))}
        for (mixer, setting, mlp), lo, hi, at in cfg.layer_runs():
            if mixer == "attention" and cfg.attention == "mha":
                counter("lm.attn.%s.%s.%d" % (
                    "window" if setting[0] else "full",
                    "rope" if setting[1] else "nope", hi - lo)).inc()
            # what is a layer's lies under the layer's own scopes; what is
            # left here is the run's slice of the stacks and the scan's own
            # traffic (a layer's leaves in, the kept values out)
            with jax.named_scope("layers"):
                run = {k: v[lo:hi] for k, v in stacked.items()}
                for kind in (mixer, mlp):
                    klo, khi = at[kind]
                    run.update({k: v[klo:khi]
                                for k, v in own[kind].items()})
                carry = layers(carry, body_of(mixer, setting), run,
                               mlp if cfg.mlp_types else mixer)
        return carry

    def held_slot_share(self, params, tokens):
        """Share of the (token, slot) pairs of all expert layers that the
        router sent to experts held here (``experts_held``)."""
        cfg = self.cfg
        n_moe = cfg.mlp_types.count("moe") or cfg.n_layers
        pairs = tokens.size * cfg.moe_top_k * n_moe
        return self._trunk(params, tokens)[1][1] / pairs

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (f32)."""
        cfg = self.cfg
        x, aux = self._trunk(params, tokens)
        if cfg.has_experts:
            aux = aux[0]

        with jax.named_scope("norm"):
            x = self._rmsnorm(x, params["final_ln_scale"])
        with jax.named_scope("head"):
            if cfg.tie_embeddings:
                logits = jnp.einsum("bte,ve->btv", x, params["embed"],
                                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.einsum("bte,ev->btv", x, params["unembed"],
                                    preferred_element_type=jnp.float32)
        return logits, aux

    def loss(self, params, tokens, targets):
        """Causal LM loss: mean token cross-entropy (+ MoE aux loss)."""
        logits, aux = self.apply(params, tokens)
        with jax.named_scope("loss"):
            nll = fused_softmax_xent(logits, targets).mean()
            return nll + self.cfg.moe_aux_weight * aux


def make_train_step(model: TransformerLM, lr=1e-2, momentum=0.9, rules=None):
    """Build a jittable SGD-momentum train step:
    (params, velocity, tokens, targets) -> (params, velocity, loss).

    Under an active mesh, jit + GSPMD turn the sharding rules into the full
    collective schedule (grad allreduce over dp, activation collectives for
    tp, ring ppermutes for sp) — the TPU-native replacement for the
    reference's kvstore push/pull training loop (`gluon/trainer.py:302`,
    `kvstore_dist.h`).

    With ``rules`` (a :class:`ShardingRules`), the updated params AND the
    momentum state are constrained to the same per-name shardings — on a
    mesh with an ``fsdp`` axis this is ZeRO-style sharded optimizer state
    (SURVEY §2.4): each device stores only its 1/fsdp slice of every
    parameter and its velocity, and XLA keeps the update math local to the
    shard.
    """
    from ..parallel.sharding import constraint

    def pin(tree):
        if rules is None:
            return tree
        return {k: constraint(v, *rules.spec_for(k))
                for k, v in tree.items()}

    def step(params, velocity, tokens, targets):
        # no scope round this: every op of the step would lie under it, and
        # the pass (``jvp`` / ``transpose``) is in each op's path already
        loss, grads = jax.value_and_grad(model.loss)(params, tokens, targets)
        with jax.named_scope("optimizer"):
            grads = pin(grads)
            new_v = pin(jax.tree_util.tree_map(
                lambda v, g: momentum * v + g.astype(v.dtype), velocity,
                grads))
            new_p = pin(jax.tree_util.tree_map(
                lambda p, v: p - lr * v.astype(p.dtype), params, new_v))
        return new_p, new_v, loss

    return step
