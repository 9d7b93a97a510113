"""Ring attention — sequence/context parallelism over an ICI ring.

Net-new capability vs the reference (SURVEY.md §5 "Long-context / sequence
parallelism — absent"; its long-sequence story stopped at BucketingModule and
SequenceMask ops).  Design (Liu et al., Ring Attention; blockwise streaming
softmax):

* the sequence dim is sharded over mesh axis ``sp``; every device holds a
  [B, T/n, H, D] slice of q, k, v;
* n ring steps: compute blockwise attention of the local q against the
  currently-held k/v block, then rotate k/v one hop around the ring
  (`lax.ppermute`) — compute and ICI transfer overlap under XLA's scheduler;
* numerically-stable streaming softmax: running max ``m``, normalizer ``l``,
  and un-normalized output accumulate across blocks exactly like flash
  attention, so the result is bit-for-bit a softmax over the *global*
  sequence;
* causal masking uses global positions (shard offset + local index);
* backward is JAX AD through the scan+ppermute (transpose of ppermute is the
  reverse rotation), with optional ``jax.checkpoint`` to avoid storing per-step
  residuals.

Scores/accumulators are f32 regardless of input dtype (MXU-friendly bf16 in,
f32 accumulate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "blockwise_attention", "ring_self_attention"]

_NEG = -1e30


def _block_scores(q, k, scale):
    # [B, Tq, H, D] x [B, Tk, H, D] -> [B, H, Tq, Tk], f32 accumulation (MXU)
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def _stream_update(o, m, l, s, v):
    """One streaming-softmax accumulation step (flash-attention recurrence)."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name, causal=True, scale=None,
                   use_pallas=False):
    """Global attention over a sequence sharded on ``axis_name``.

    Must be called inside ``shard_map`` (or pmap) with ``axis_name`` bound.
    q, k, v: [B, T_local, H, D] per-shard slices.  Returns [B, T_local, H, D].

    ``use_pallas`` swaps the pure-lax per-block streaming update for the
    Pallas flash kernel as the block kernel (ROADMAP item 3 slice): every
    ring step runs ``ops.pallas.flash_attention_lse`` on the held k/v
    block and the normalized block outputs are merged with the
    flash-decoding logsumexp recurrence — numerically the same global
    softmax.  Off-TPU it falls back to the lax block kernel
    (``use_pallas="interpret"`` forces the real kernels through the
    Pallas interpreter for CPU parity tests).  Trainable end-to-end:
    `flash_attention_lse` carries a custom VJP over both outputs (the lse
    cotangent folds into the backward kernels' delta operand), so JAX AD
    through the merge + scan + ppermute gives the exact global-attention
    gradient — see tests/test_parallel.py's train-step parity tests.
    """
    if use_pallas:
        return _ring_attention_flash(q, k, v, axis_name, causal, scale,
                                     interpret=(use_pallas == "interpret"))
    B, Tq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    Tk = k.shape[1]
    q_pos = my * Tq + jnp.arange(Tq)

    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)

    def block(o, m, l, k_blk, v_blk, owner):
        s = _block_scores(q, k_blk, scale)
        if causal:
            k_pos = owner * Tk + jnp.arange(Tk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG)
        return _stream_update(o, m, l, s, v_blk)

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        # rotate first: receive the block owned by (my + i) from the next
        # rank (shift -1 around the ring); n-1 rotations total — the local
        # block was consumed before the scan
        from .collectives import ppermute_shift
        with jax.named_scope("ring_kv"):
            k_blk = ppermute_shift(k_blk, axis_name, -1)
            v_blk = ppermute_shift(v_blk, axis_name, -1)
        o, m, l = block(o, m, l, k_blk, v_blk, (my + i) % n)
        return (o, m, l, k_blk, v_blk), None

    o, m, l = block(o0, m0, l0, k, v, my)
    (o, m, l, _, _), _ = lax.scan(
        jax.checkpoint(step), (o, m, l, k, v), jnp.arange(1, n))
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _merge_partials(o_a, lse_a, o_b, lse_b):
    """Merge two normalized softmax partials (flash-decoding recurrence).

    o_*: [B, T, H, D] f32 normalized outputs over disjoint key sets,
    lse_*: [B, H, T] f32 logsumexp of the (scaled, masked) scores over the
    same key sets.  A fully-masked partial carries lse = _NEG and therefore
    contributes weight exp(_NEG - lse_new) = 0.
    """
    lse_new = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse_new).transpose(0, 2, 1)[..., None]
    w_b = jnp.exp(lse_b - lse_new).transpose(0, 2, 1)[..., None]
    return o_a * w_a + o_b * w_b, lse_new


def _ring_attention_flash(q, k, v, axis_name, causal, scale, interpret):
    """Ring attention with the Pallas flash kernel as the block kernel.

    Same ring schedule as the lax path, but each held k/v block is consumed
    by one `flash_attention_lse` call (normalized output + logsumexp) and
    blocks are combined with `_merge_partials`.  Causality across shards is
    exact at block granularity: every q position on shard `my` may attend
    the *entire* block of any owner < my, no position of any owner > my,
    and the diagonal block is handled by the kernel's own causal mask — so
    remote blocks run the cheaper non-causal kernel and future-owner blocks
    are killed via lse = _NEG before the merge.

    Differentiable: the block kernel's custom VJP covers both (o, lse), and
    the ring step is rematerialized (``jax.checkpoint``, matching the lax
    path) so the backward re-runs each block kernel instead of storing
    per-step residuals.
    """
    from ..ops.pallas import flash_attention_lse
    from .collectives import ppermute_shift

    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)

    def blk(k_blk, v_blk, blk_causal):
        o, lse = flash_attention_lse(
            q, k_blk, v_blk, causal=blk_causal, scale=scale,
            interpret=(True if interpret else None), per_device=True)
        return o.astype(jnp.float32), lse

    o, lse = blk(k, v, causal)

    def step(carry, i):
        o, lse, k_blk, v_blk = carry
        with jax.named_scope("ring_kv"):
            k_blk = ppermute_shift(k_blk, axis_name, -1)
            v_blk = ppermute_shift(v_blk, axis_name, -1)
        o_b, lse_b = blk(k_blk, v_blk, False)
        if causal:
            owner = (my + i) % n
            lse_b = jnp.where(owner < my, lse_b, _NEG)
        o, lse = _merge_partials(o, lse, o_b, lse_b)
        return (o, lse, k_blk, v_blk), None

    (o, lse, _, _), _ = lax.scan(
        jax.checkpoint(step), (o, lse, k, v), jnp.arange(1, n))
    return o.astype(q.dtype)


def blockwise_attention(q, k, v, block_size=512, causal=True, scale=None,
                        return_lse=False, window=None):
    """Single-device memory-efficient attention: lax.scan over key blocks with
    the same streaming-softmax recurrence (O(T) memory in sequence length).
    The in-shard counterpart of `ring_attention`; also the CPU/interpret
    fallback for the Pallas flash kernel.

    ``return_lse=True`` additionally returns the per-row logsumexp
    [B, H, T] of the scaled masked scores (fully-masked rows get ``_NEG``),
    matching `ops.pallas.flash_attention_lse` so either can serve as a
    flash-decoding block kernel.  ``window`` (with ``causal``): a query sees
    the keys ``0 <= t - j < window`` alone, as the kernels' own mask."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    nb = max(1, -(-T // block_size))
    pad = nb * block_size - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, block_size, H, D)
    vb = v.reshape(B, nb, block_size, H, v.shape[-1])
    q_pos = jnp.arange(T)

    o0 = jnp.zeros((B, T, H, v.shape[-1]), jnp.float32)
    m0 = jnp.full((B, H, T), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)

    def step(carry, blk):
        o, m, l = carry
        k_blk, v_blk, bi = blk
        s = _block_scores(q, k_blk, scale)
        k_pos = bi * block_size + jnp.arange(block_size)
        valid = k_pos < T
        mask = valid[None, :]
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(mask[None, None], s, _NEG)
        o, m, l = _stream_update(o, m, l, s, v_blk)
        return (o, m, l), None

    (o, m, l), _ = lax.scan(step, (o0, m0, l0),
                            (kb.swapaxes(0, 1), vb.swapaxes(0, 1),
                             jnp.arange(nb)))
    out = (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    if return_lse:
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG)
        return out, lse
    return out


def ring_self_attention(q, k, v, mesh=None, seq_axis="sp", batch_axis="dp",
                        head_axis="tp", causal=True, use_pallas=False):
    """Convenience SPMD wrapper: q/k/v [B, T, H, D] with batch sharded on
    ``batch_axis``, sequence on ``seq_axis``, heads on ``head_axis`` (ring
    attention is per-head, so head sharding composes transparently).  Falls
    back to plain blockwise attention when the mesh has no ``sp`` axis.
    ``use_pallas`` selects the Pallas flash block kernel (see
    `ring_attention`); the no-``sp`` fallback then routes through
    `ops.pallas.flash_attention` (which itself falls back off-TPU)."""
    from .mesh import current_mesh
    from jax.sharding import PartitionSpec as P
    from .collectives import shard_map

    mesh = mesh or current_mesh()
    if mesh is None or mesh.size(seq_axis) == 1:
        if use_pallas:
            from ..ops.pallas import flash_attention
            return flash_attention(
                q, k, v, causal=causal,
                interpret=(True if use_pallas == "interpret" else None))
        return blockwise_attention(q, k, v, causal=causal)

    def ax(name):
        return name if mesh.size(name) > 1 else None

    spec = P(ax(batch_axis), seq_axis, ax(head_axis), None)
    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                           use_pallas=use_pallas)
    return shard_map(fn, mesh=mesh.mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
