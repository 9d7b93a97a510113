"""Mixture-of-experts layers.

Net-new vs the reference (SURVEY.md §2.4 lists expert parallelism/MoE as
absent).  Two layers live here:

* :func:`expert_layer` -- what ``TransformerLM`` runs: a router over all the
  experts the model has, top-k without a capacity, and the part of the
  result that the experts *held here* give.  The (token, slot) pairs are
  sorted by expert, the held experts' pairs first, group after group; each
  group's rows go through its expert's matrices as one grouped product
  (`ops/pallas/grouped_matmul.py`), and the rows come back to their tokens
  weighted by the router: the combine, one operation (``_combine``) whose
  backward is written in the sorted rows' space -- a row's gradient is its
  weight times its token's, read by token as the dispatch's forward reads
  -- and whose residual, the down product's rows, carries a
  ``checkpoint_name`` (``SAVED_NAMES``) so that a rematerialised layer
  keeps them.  On the chip the dispatch's and the combine's row gathers
  move only the held experts' pairs (``ops/pallas/moe_gather.py``, where
  ``moe_gather.select`` says so).  No token is dropped whatever the
  imbalance, no product is computed for a pair whose expert is not held
  (beyond a row tile's rounding), and what the absent experts would have added is left
  out: with ``experts_held`` a chip's share of an expert-parallel layer,
  that partial result is what the chip has before the exchange.  The
  exchange itself is not written (ROADMAP R-m3).
* :func:`moe_layer` -- the older GShard-style einsum dispatch with a capacity
  factor (overflow tokens are dropped), whose dense one-hot masks GSPMD
  shards over the mesh axis ``ep``: what a model under an ``ep``-sharded
  mesh still gets until that exchange exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas import moe_gather
from ..ops.pallas.grouped_matmul import grouped_matmul
from .sharding import constraint

__all__ = ["expert_layer", "moe_layer", "route", "SAVED_NAMES"]

# the ``checkpoint_name`` of the down product's rows as the combine's
# residual: what a rematerialised layer keeps of an expert layer, so that it
# re-makes neither the down product nor a gather of its rows
# (``models.transformer.KEPT``)
SAVED_NAMES = ("moe_down_rows",)


def route(tokens, router_w, top_k, renormalize=False, seq_shape=None,
          scoring="softmax", bias=None):
    """The router, in float32 whatever the model's dtype: ``s = softmax(
    tokens . router_w)`` over all experts, the ``top_k`` largest of each
    token (greedy) as ``(weights [S, k], experts [S, k])``, and the
    load-balancing term of each sequence, averaged: ``sum_i f_i P_i`` with
    ``f_i`` the slots routed to expert ``i`` times ``n / (k T)`` (a count:
    no gradient) and ``P_i`` the mean of ``s_i`` over the sequence.
    ``seq_shape`` is ``(B, T)`` of the flattened ``tokens`` [S, E].

    ``scoring="sigmoid"``: ``s = sigmoid(tokens . router_w)``, each expert's
    score its own.  ``bias`` [n] (float32): the experts are the ``top_k``
    largest of ``s + bias``, their weights the unbiased ``s``; renormalised
    they are divided by their sum plus 1e-6.  The bias takes no gradient."""
    n = router_w.shape[1]
    S = tokens.shape[0]
    B, T = seq_shape or (1, S)
    logits = jnp.einsum("se,en->sn", tokens.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "softmax" and bias is None:
        s = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(s, top_k)
        if renormalize and top_k > 1:
            weights = weights / jnp.maximum(
                weights.sum(-1, keepdims=True), 1e-9)
    else:
        assert scoring in ("softmax", "sigmoid"), scoring
        s = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        choose = s if bias is None else s + lax.stop_gradient(
            bias.astype(jnp.float32))
        _, experts = lax.top_k(choose, top_k)
        weights = jnp.take_along_axis(s, experts, axis=-1)
        if renormalize and top_k > 1:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    counts = jax.nn.one_hot(experts.reshape(B, T * top_k), n,
                            dtype=jnp.float32).sum(axis=1)       # [B, n]
    f = lax.stop_gradient(counts) * (n / (top_k * T))
    aux = jnp.mean(jnp.sum(f * s.reshape(B, T, n).mean(axis=1), axis=-1))
    return weights, experts, aux


def _slots(rows, inverse, k):
    """``rows[inverse]`` [P, E] slot-major, [k, S, E]: ``[j, s]`` is the
    sorted row of pair ``s k + j``.  With the slots leading, a tiled layout
    pads nothing and ``[P, E]`` to ``[k, S, E]`` moves no byte; token-major,
    ``[S, k, E]``, a ``k`` of 6 is padded to the tile's rows and every
    reshape from or to ``[P, E]`` is a copy (PERF.md §6, PR 37)."""
    return rows[inverse.reshape(-1, k).T]


def _sum_slots(slots, weights=None):
    """The float32 sum over the slots of ``slots`` [k, S, E], each times its
    weight (``weights`` [S, k]; None: 1) -> [S, E] float32.  Term by term:
    one elementwise pass over ``k`` slices, where a reduction over the
    leading axis left the float32 copy of ``slots`` a pass of its own
    (PERF.md §6, PR 37)."""
    total = None
    for j in range(slots.shape[0]):
        term = slots[j].astype(jnp.float32)
        if weights is not None:
            term = term * weights[:, j, None]
        total = term if total is None else total + term
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 5))
def _to_sorted(tokens, order, inverse, k, live=None, impl="fallback"):
    """tokens [S, E] -> the row of each (token, slot) pair in sorted order
    [S k, E]: pair ``p`` is token ``p // k``.  ``live`` int32 [1]: the pairs
    whose expert is held, which sort first; with the kernels (``impl``, from
    ``moe_gather.select``) only the blocks that hold them are written."""
    if impl == "fallback":
        return tokens[order // k]
    return moe_gather.sorted_rows(tokens, order // k, live,
                                  interpret=impl == "interpret")


def _to_sorted_fwd(tokens, order, inverse, k, live, impl):
    return _to_sorted(tokens, order, inverse, k, live, impl), (inverse, live)


def _to_sorted_bwd(k, impl, res, g):
    # the transpose of a permutation is a gather by its inverse, not a
    # scatter; a token's gradient is the sum over its slots: the combine's
    # forward with unit weights
    inverse, live = res
    if impl == "fallback":
        d = _sum_slots(_slots(g, inverse, k))
    else:
        d = moe_gather.slot_sum(g, inverse, live, None, k,
                                interpret=impl == "interpret")
    return d.astype(g.dtype), None, None, None


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(out, weights, order, inverse, live=None, impl="fallback"):
    """The sorted rows back at their tokens, weighted: ``y[s] = sum_j
    weights[s, j] * out[inverse[s k + j]]`` in float32, in ``out``'s dtype
    [S, E].  Its backward stays in the sorted rows' space (no ``[S, k, E]``
    gradient is built): the gradient of sorted row ``r`` is ``w[r] *
    g[token(r)]``, a gather from ``g`` [S, E] by the index the dispatch's
    forward uses, and a weight's is its row's product with that.  With the
    kernels both read only the first ``live`` rows."""
    k = weights.shape[1]
    if impl != "fallback":
        with jax.named_scope("moe.combine.sum"):
            return moe_gather.slot_sum(out, inverse, live, weights, k,
                                       interpret=impl == "interpret")
    with jax.named_scope("moe.combine.gather"):
        slots = _slots(out, inverse, k)
    with jax.named_scope("moe.combine.sum"):
        return _sum_slots(slots, weights).astype(out.dtype)


def _combine_fwd(out, weights, order, inverse, live, impl):
    # the rows are named as the residual alone: a kept value that the
    # forward reads too gets a ``reduce_precision`` from ``jax.checkpoint``,
    # which behind a kernel is a copy of the rows
    return _combine(out, weights, order, inverse, live, impl), (
        checkpoint_name(out, SAVED_NAMES[0]), weights, order, inverse, live)


def _combine_bwd(impl, res, g):
    out, weights, order, inverse, live = res
    k = weights.shape[1]
    with jax.named_scope("moe.combine.gather"):
        # the weights to the sorted rows, and below their gradients back:
        # each a permutation by a sort on the other index (a gather of P
        # scalars costs XLA about seven times a sort's time on the chip);
        # the keys are distinct, and a stable sort would hold an iota of P
        # from the forward on (the step's peak)
        w_rows = lax.sort_key_val(inverse, weights.reshape(-1),
                                  is_stable=False)[1]
        if impl == "fallback":
            g_rows = _to_sorted(g, order, inverse, k, live, impl
                                ).astype(jnp.float32)
        else:
            d_out, d_w_rows = moe_gather.sorted_rows_grad(
                g, order // k, live, out, w_rows,
                interpret=impl == "interpret")
    with jax.named_scope("moe.combine.sum"):
        if impl == "fallback":
            # rows past the groups are zero in ``out``: an absent expert's
            # slot gets a zero weight gradient, and what ``d_out`` holds
            # there is never read (the grouped product's tail and row mask)
            d_out = (w_rows[:, None] * g_rows).astype(out.dtype)
            d_w_rows = jnp.sum(out.astype(jnp.float32) * g_rows, axis=-1)
        d_w = lax.sort_key_val(order, d_w_rows, is_stable=False)[1]
        if impl != "fallback":
            # the kernel leaves ``d_w`` unwritten past the live rows: an
            # absent expert's slot gets a zero weight gradient
            d_w = jnp.where(inverse < live[0], d_w, 0.0)
        d_weights = d_w.reshape(weights.shape).astype(weights.dtype)
    return d_out, d_weights, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def expert_layer(x, router_w, w_up, w_down, w_gate=None, top_k=1,
                 experts_held=None, renormalize=True, act=None,
                 routing=None, routed_scale=1.0):
    """The held experts' part of a routed feed-forward, no token dropped.

    x: [B, T, E]; router_w: [E, n] over all ``n`` experts; w_up (and
    w_gate): [held, E, F]; w_down: [held, F, E], stacked in the order of
    ``experts_held`` (ids among ``0 .. n - 1``; None: all of them).  An
    expert is ``down(act(up(h)))``, or with ``w_gate`` ``down(act(gate(h))
    * up(h))``; ``act`` None is ``gelu`` for the first form and ``silu``
    for the gated one.  ``routing`` is :func:`route`'s result where the
    router did not read the rows dispatched here (one placed before
    attention reads the layer's input, and has run by now); None: the
    router reads ``x``.  ``routed_scale`` multiplies every weight, and so
    the routed sum.  Returns ``(y [B, T, E], aux, held)``: ``y_t = sum
    over the slots of t whose expert is held of w * expert(x_t)``, the router's
    balance term (over all ``n``, so every share computes it alike), and
    how many of the ``B T k`` pairs landed on held experts."""
    from .. import telemetry as _telemetry
    B, T, E = x.shape
    n = router_w.shape[1]
    k = int(top_k)
    assert 1 <= k <= n, "top_k must be in [1, n_experts]"
    held = tuple(range(n)) if experts_held is None else tuple(experts_held)
    assert len(set(held)) == len(held) == w_up.shape[0] and all(
        0 <= e < n for e in held), "experts_held names the stacked experts"
    S, P, n_held = B * T, B * T * k, len(held)
    reg = _telemetry.registry()
    reg.counter("moe.experts_held.%dof%d" % (n_held, n)).inc()
    reg.counter("moe.buffer_rows.%d" % P).inc()
    reg.counter("moe.combine.rows_kept.%dx%d" % (P, E)).inc()

    tokens = x.reshape(S, E)
    if act is None:
        act = jax.nn.gelu if w_gate is None else jax.nn.silu
    if routing is None:
        with jax.named_scope("moe.route"):
            routing = route(tokens, router_w, k, renormalize, (B, T))
    weights, experts, aux = routing
    if routed_scale != 1:
        reg.counter("moe.routed_scale.%g" % routed_scale).inc()
        with jax.named_scope("moe.route"):
            weights = weights * routed_scale
    with jax.named_scope("moe.dispatch"):
        with jax.named_scope("moe.dispatch.sort"):
            # a pair's key is its expert's place in the stack; an absent
            # expert's pairs sort behind every group and are never computed
            place = np.full((n,), n_held, np.int32)
            place[list(held)] = np.arange(n_held, dtype=np.int32)
            key = jnp.asarray(place)[experts.reshape(P)]
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            sizes = jnp.sum(
                key[:, None] == jnp.arange(n_held, dtype=jnp.int32),
                axis=0, dtype=jnp.int32)
            live = jnp.sum(sizes)[None]
        impl = moe_gather.select(S, k, E, x.dtype)
        with jax.named_scope("moe.dispatch.gather"):
            rows = _to_sorted(tokens, order, inverse, k, live, impl)
    # the lax combine sums every slot's row, so an absent expert's rows
    # must be zero; the kernels read only the first ``live`` rows of each
    # buffer, so the products leave the rest unwritten
    product = functools.partial(
        grouped_matmul, group_sizes=sizes,
        rows_past_groups="zero" if impl == "fallback" else "unread")
    with jax.named_scope("moe.experts"):
        up = product(rows, w_up).astype(jnp.float32)
        if w_gate is not None:
            gate = product(rows, w_gate).astype(jnp.float32)
        with jax.named_scope("moe.experts.act"):
            up = (act(up) if w_gate is None else act(gate) * up
                  ).astype(x.dtype)
        out = product(up, w_down)
    with jax.named_scope("moe.combine"):
        y = _combine(out, weights, order, inverse, live, impl)
    return y.reshape(B, T, E), aux, live[0].astype(jnp.float32)


def moe_layer(x, gate_w, w_up, w_down, ep_axis="ep", capacity_factor=1.25,
              top_k=1, renormalize=True, act=jax.nn.relu):
    """Top-k routed MoE feed-forward with a capacity (see the module's
    docstring: the layer of an ``ep``-sharded mesh).

    x: [B, T, E]; gate_w: [E, n_exp]; w_up: [n_exp, E, H];
    w_down: [n_exp, H, E].  Returns (y [B, T, E], aux_loss scalar).

    ``top_k=1`` is the Switch Transformer router; ``top_k>=2`` the
    GShard router (each token dispatches to its k best experts; with
    ``renormalize`` the kept gate values are rescaled to sum to 1).
    Tokens overflowing an expert's capacity are dropped for that slot —
    the standard static-shape MoE contract.
    """
    B, T, E = x.shape
    n_exp = gate_w.shape[1]
    k = int(top_k)
    assert 1 <= k <= n_exp, "top_k must be in [1, n_experts]"
    S = B * T
    capacity = max(1, int(capacity_factor * k * S / n_exp))

    tokens = x.reshape(S, E)
    logits = jnp.einsum("se,en->sn", tokens, gate_w,
                        preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                       # [S, n]
    topg, tope = jax.lax.top_k(gates, k)                          # [S, k]
    if renormalize and k > 1:
        topg = topg / jnp.maximum(topg.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(tope, n_exp, dtype=gates.dtype)       # [S,k,n]

    # load-balancing aux loss (Switch Transformer eq. 4, over the
    # primary expert choice)
    density = onehot[:, 0, :].mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux_loss = n_exp * jnp.sum(density * density_proxy)

    # capacity: queue position of each (token, slot) inside its expert,
    # counted in (slot-major, token) order so primary routes win slots
    flat = onehot.transpose(1, 0, 2).reshape(k * S, n_exp)        # [kS, n]
    pos = jnp.cumsum(flat, axis=0) * flat                         # [kS, n]
    keep = (pos <= capacity) & (flat > 0)
    pos_idx = jnp.clip(pos.sum(-1).astype(jnp.int32) - 1, 0,
                       capacity - 1)                              # [kS]

    # dispatch mask [kS, n, c] -> expert inputs [n, c, E]
    disp = (keep.astype(tokens.dtype)[:, :, None]
            * jax.nn.one_hot(pos_idx, capacity,
                             dtype=tokens.dtype)[:, None, :])
    tokens_k = jnp.broadcast_to(tokens[None], (k, S, E)).reshape(
        k * S, E)
    expert_in = jnp.einsum("znc,ze->nce", disp, tokens_k)
    expert_in = constraint(expert_in, ep_axis, None, None)

    h = jnp.einsum("nce,neh->nch", expert_in, w_up,
                   preferred_element_type=jnp.float32)
    h = act(h).astype(x.dtype)
    expert_out = jnp.einsum("nch,nhe->nce", h, w_down,
                            preferred_element_type=jnp.float32
                            ).astype(x.dtype)
    expert_out = constraint(expert_out, ep_axis, None, None)

    # combine: per-slot gather weighted by the kept gate value
    gate_flat = topg.transpose(1, 0).reshape(k * S)               # [kS]
    y_flat = jnp.einsum("znc,nce->ze", disp, expert_out) \
        * gate_flat[:, None].astype(x.dtype)
    y = y_flat.reshape(k, S, E).sum(axis=0)
    return y.reshape(B, T, E), aux_loss
