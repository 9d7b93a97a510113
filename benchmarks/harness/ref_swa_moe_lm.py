"""Plain reference of the window-and-full-attention, routed-experts LM the
benchmark's SmallThinker-sized configuration runs.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no sort, no cache.  It imports nothing of the program and is handed
the weights the benchmark made.  A layer on the residual stream ``x`` [T, E]
(pre-norm, two residuals, RMSNorm eps 1e-6, no biases, no q/k norm; the
embedding unscaled, a final RMSNorm, the head untied):

* ``r = x W_r`` in float32: **the router reads the layer's input, before
  the first norm and before attention**;
* ``h = rms1(x)``; ``q, k, v = h W_qkv`` as ``H`` / ``KV`` / ``KV`` heads
  of ``D`` (``H D`` need not be ``E``); on a layer whose ``attn_rope`` entry
  is 1 ``q`` and ``k`` are rotated (rotate-half over all ``D`` columns:
  column ``i`` pairs with ``i + D / 2``; ``theta_i = base^(-2 i / D)``,
  positions 0 .. T - 1, float32), on the others nothing;
* ``a_t = softmax_j(q_t . k_j / sqrt(D)) v_j`` over ``j <= t``, or on a
  layer whose ``attn_windows`` entry is ``W`` > 0 over ``0 <= t - j < W``
  (the mask written out), each key/value head serving ``H / KV`` query
  heads; ``x' = x + a W_o``;
* ``g = rms2(x')``; the ``k`` experts of token ``t`` are the ``k`` largest
  of ``softmax(r_t)``, their weights renormalised to sum to one (the softmax
  over those ``k`` logits); expert ``e``: ``(relu(g W_gate,e) * (g W_up,e))
  W_down,e`` (ReGLU); ``y = x' + sum over the slots whose expert is *held*
  of w f_e(g_t)``.  The experts are a plain loop over the held ones, each
  applied to every token and weighted by the router's weight for it (zero
  where the token did not choose it).  **What an absent expert would have
  added is left out**, as in the program: the configuration is one chip's
  share of an expert-parallel layer.  The balance term of a sequence is
  ``sum_i f_i P_i``, ``f_i`` = slots routed to ``i`` times ``n / (k T)`` (a
  count), ``P_i`` the sequence's mean of ``softmax(r)_i``, over all ``n``
  experts; the loss is the mean cross-entropy plus ``moe_aux_weight`` times
  the sum over the layers of the sequences' mean balance term.

So that 16,384 tokens fit a chip, attention is computed a key/value head's
group of query heads and a block of queries at a time (every key each time:
the mask decides) and the loss a block of rows at a time; the mathematics is
the plain one.

Training follows the configuration's optimizer: SGD with momentum on
parameters and momentum *stored* in the model's type: ``v <- m v + g``, ``p
<- p - lr v``, each rounded once to storage.  The state is kept a layer at a
time and each layer is updated as soon as its gradient is known.
``operand`` swaps in the control's rounding on the operands of every matrix
product but the router's (which the configuration states in float32), and
``fault`` plants a fault, so the same code gives the readings the limits are
set from: ``half_batch`` (half the step's tokens), ``no_window`` (every
layer attends over the whole causal prefix), ``router_after_attention``
(the router reads ``rms2(x')``, the rows the experts are given).
``held_shares`` records, a step, the share of (token, slot) pairs that
landed on held experts.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .ref_mla_moe_lm import route, sgd_momentum
from .ref_transformer import OPERANDS, _f32, _sq, rmsnorm

BLOCK = ("ln1_scale", "ln2_scale", "wqkv", "wo", "gate", "moe_gate",
         "moe_up", "moe_down")
OUTER = ("embed", "final_ln_scale", "unembed")
FAULTS = ("half_batch", "no_window", "router_after_attention")
_QUERY_BLOCK = 1024          # queries a block of the attention
_LOSS_BLOCK = 2048           # rows a block of the loss


def _block_of(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def rope_tables(m, t):
    """cos, sin [t, D] float32: pair i's angle in columns i and i + D/2."""
    d = m["head_dim"]
    inv = float(m["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    angle = np.concatenate([angle, angle], axis=-1)
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rope(x, cos, sin):
    """x [T, heads, D]; cos, sin [T, D]."""
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos[:, None, :] + jnp.concatenate([-b, a], -1) * sin[:, None, :]


def attention(q, k, v, window, q_):
    """q [T, H, D], k, v [T, KV, D] -> [T, H D]: causal softmax attention,
    over the keys ``0 <= t - j < window`` where ``window`` > 0."""
    t, heads, d = q.shape
    kv = k.shape[1]
    group, qb = heads // kv, _block_of(t, _QUERY_BLOCK)
    # [KV, blocks, qb, group, D]: a key/value head's query heads together
    qg = q.reshape(t // qb, qb, kv, group, d).transpose(2, 0, 1, 3, 4)
    starts = jnp.arange(t // qb, dtype=jnp.int32) * qb
    j = jnp.arange(t, dtype=jnp.int32)[None, :]

    def one_head(args):
        q_head, k_head, v_head = args        # [blocks, qb, G, D], [T, D] x 2

        @jax.checkpoint
        def one_block(block):
            q_blk, start = block                           # [qb, G, D]
            at = start + jnp.arange(qb, dtype=jnp.int32)[:, None]
            gap = at - j                                   # t - j  [qb, T]
            seen = gap >= 0
            if window:
                seen = seen & (gap < window)
            s = jnp.einsum("qgd,kd->gqk", q_(q_blk), q_(k_head)
                           ) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
            return jnp.einsum("gqk,kd->qgd", q_(p), q_(v_head))

        return jax.lax.map(one_block, (q_head, starts))    # [blocks, qb, G, D]

    out = jax.lax.map(one_head, (qg, k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))
    # [KV, blocks, qb, G, D] -> [T, KV G D]: head h is group h // G
    return out.transpose(1, 2, 0, 3, 4).reshape(t, heads * d)


def reglu(h, w_gate, w_up, w_down, q_):
    gate = jnp.einsum("...e,ef->...f", q_(h), q_(w_gate))
    up = jnp.einsum("...e,ef->...f", q_(h), q_(w_up))
    return jnp.einsum("...f,fe->...e", q_(jnp.maximum(gate, 0.0) * up),
                      q_(w_down))


def held_experts(lp, g, weights, experts, held, q_):
    """``sum over the held experts of w_e(t) f_e(g_t)``: a plain loop."""
    @jax.checkpoint
    def one(y, xs):
        w_gate, w_up, w_down, expert = xs
        # the router's weight of this expert for each token, 0 where the
        # token did not choose it
        w = jnp.sum(jnp.where(experts == expert, weights, 0.0), axis=-1)
        return y + w[..., None] * reglu(g, w_gate, w_up, w_down, q_), None

    return jax.lax.scan(one, jnp.zeros_like(g),
                        (lp["moe_gate"], lp["moe_up"], lp["moe_down"],
                         jnp.asarray(held, jnp.int32)))[0]


def layer(lp, x, setting, m, operand=None, fault=None):
    """One layer on x [B, T, E] -> (x, balance term, pairs on held).
    ``setting`` is the layer's ``(window, rotary)``."""
    q_ = OPERANDS[operand]
    window, turned = setting
    if fault == "no_window":
        window = 0
    b, t, e = x.shape
    heads, kv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    held = list(m["experts_held"]) or list(range(m["n_experts"]))
    if fault != "router_after_attention":
        routing = route(lp, x, m)            # the layer's input, un-normed
    h = rmsnorm(x, lp["ln1_scale"])
    qkv = jnp.einsum("bte,ef->btf", q_(h), q_(lp["wqkv"]))
    q, k, v = jnp.split(qkv, [heads * d, (heads + kv) * d], axis=-1)
    q = q.reshape(b, t, heads, d)
    k = k.reshape(b, t, kv, d)
    v = v.reshape(b, t, kv, d)
    if turned:
        cos, sin = rope_tables(m, t)
        q = jax.vmap(lambda a: rope(a, cos, sin))(q)
        k = jax.vmap(lambda a: rope(a, cos, sin))(k)
    attn = jax.lax.map(lambda row: attention(*row, window, q_), (q, k, v))
    x = x + jnp.einsum("btf,fe->bte", q_(attn), q_(lp["wo"]))
    g = rmsnorm(x, lp["ln2_scale"])
    if fault == "router_after_attention":
        routing = route(lp, g, m)
    weights, experts, aux = routing
    y = held_experts(lp, g, weights, experts, held, q_)
    on_held = jnp.sum(jnp.isin(experts, jnp.asarray(held, jnp.int32)))
    return x + y, aux, on_held.astype(jnp.float32)


def head_loss_sum(hp, x, targets, operand=None):
    """Sum over all tokens of the cross-entropy; x [B, T, E], a block of
    rows at a time."""
    q_ = OPERANDS[operand]
    e = x.shape[-1]
    rows = x.reshape(-1, e)
    rb = _block_of(rows.shape[0], _LOSS_BLOCK)

    @jax.checkpoint
    def block(args):
        xr, tr = args
        h = rmsnorm(xr, hp["final_ln_scale"])
        logits = jnp.einsum("ne,ev->nv", q_(h), q_(hp["unembed"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)
    return jnp.sum(jax.lax.map(block, (rows.reshape(-1, rb, e),
                                       targets.reshape(-1, rb))))


def settings(m):
    """[(window, rotary)] a layer."""
    return [(int(w), bool(r)) for w, r in zip(m["attn_windows"],
                                              m["attn_rope"])]


def forward_loss(m, params, tokens, operand=None, fault=None):
    """The whole loss in one piece (tests at toy sizes): ``params`` the flat
    dict, ``tokens`` [B, T + 1]."""
    x = params["embed"][tokens[:, :-1]].astype(jnp.float32)
    aux = 0.0
    for i, setting in enumerate(settings(m)):
        lp = {k: params["blocks." + k][i] for k in BLOCK}
        x, a, _ = layer(_f32(lp), x, setting, m, operand, fault)
        aux = aux + a
    n = tokens[:, 1:].size
    nll = head_loss_sum(_f32({k: params[k] for k in OUTER}), x,
                        tokens[:, 1:], operand) / n
    return nll + m.get("moe_aux_weight", 0.01) * aux


class TrainReference:
    """The reference trainer.  ``params`` is the flat dict the benchmark
    made; it is split into layers (and copied) here."""

    def __init__(self, model, params, optimizer, device=None, operand=None,
                 fault=None):
        assert fault is None or fault in FAULTS, \
            "no fault %r: this family plants %s" % (fault, ", ".join(FAULTS))
        self.m = m = model
        self.lr = float(optimizer["lr"])
        self.mom = float(optimizer["momentum"])
        self.fault = fault
        self.alpha = float(m.get("moe_aux_weight", 0.01))
        self.settings = settings(m)
        self.home = device or jax.devices()[0]
        put = lambda a: jax.device_put(a, self.home)
        self.layers = [{k: put(params["blocks." + k][i]) for k in BLOCK}
                       for i in range(len(self.settings))]
        # a copy: the updates donate these, and the caller keeps its own
        self.outer = {k: put(jnp.copy(params[k])) for k in OUTER}
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        self.v_layers = [zeros(lp) for lp in self.layers]
        self.v_outer = zeros(self.outer)
        self.first_grad_sq = None
        self.held_shares = []
        # the jitted functions below hold no ``self``: a trainer that is
        # dropped frees its state at once, with no cycle to collect
        alpha = jnp.float32(self.alpha)
        update = functools.partial(sgd_momentum, self.mom, self.lr)

        def fwd(setting):
            return jax.jit(lambda lp, x: layer(_f32(lp), x, setting, m,
                                               operand, fault))

        def bwd_update(setting):
            def fn(lp, lv, x, dx):
                _, vjp = jax.vjp(lambda p, x_: layer(p, x_, setting, m,
                                                     operand, fault)[:2],
                                 _f32(lp), x)
                g, dx_in = vjp((dx, alpha))
                new_p, new_v = update(lp, lv, g)
                return dx_in, new_p, new_v, {k: _sq(a) for k, a in g.items()}
            return jax.jit(fn, donate_argnums=(0, 1))

        def head(hp, x, targets, n_tokens):
            def loss_fn(hp32, x_):
                return head_loss_sum(hp32, x_, targets, operand) / n_tokens
            loss, vjp = jax.vjp(loss_fn, _f32(hp), x)
            g_hp, dx = vjp(jnp.float32(1.0))
            return loss, dx, g_hp["final_ln_scale"], g_hp["unembed"]

        def outer_update(outer, v_outer, g_scale, g_head, tokens, dx0):
            g = {"final_ln_scale": g_scale, "unembed": g_head,
                 "embed": jnp.zeros(outer["embed"].shape, jnp.float32
                                    ).at[tokens].add(dx0)}
            new_p, new_v = update(outer, v_outer, g)
            return new_p, new_v, {k: _sq(a) for k, a in g.items()}

        kinds = sorted(set(self.settings))
        self._fwd = {s: fwd(s) for s in kinds}
        self._bwd = {s: bwd_update(s) for s in kinds}
        self._head = jax.jit(head, static_argnums=(3,))
        self._outer_update = jax.jit(outer_update, donate_argnums=(0, 1))
        self._lookup = jax.jit(lambda e, t: e[t].astype(jnp.float32))

    def step(self, tokens):
        """One step on tokens [B, T + 1]; returns the loss as a float."""
        tokens = np.asarray(tokens)
        if self.fault == "half_batch":
            # half the step's tokens: half the rows, or of a single row the
            # first half
            if tokens.shape[0] > 1:
                tokens = tokens[: tokens.shape[0] // 2]
            else:
                tokens = tokens[:, : (tokens.shape[1] - 1) // 2 + 1]
        x_ids = jnp.asarray(tokens[:, :-1])
        y_ids = jnp.asarray(tokens[:, 1:])
        with jax.default_matmul_precision("highest"):
            x = self._lookup(self.outer["embed"], x_ids)
            xs, aux_sum, on_held = [], 0.0, 0.0
            for setting, lp in zip(self.settings, self.layers):
                xs.append(x)
                x, aux, held = self._fwd[setting](lp, x)
                aux_sum += float(aux)
                on_held += float(held)
            nll, dx, g_scale, g_head = self._head(
                self.outer, x, y_ids, int(y_ids.size))
            sq = {}
            for i in reversed(range(len(self.layers))):
                dx, self.layers[i], self.v_layers[i], gsq = \
                    self._bwd[self.settings[i]](
                        self.layers[i], self.v_layers[i], xs[i], dx)
                xs[i] = None
                if self.first_grad_sq is None:
                    for k, v in gsq.items():
                        sq["blocks." + k] = sq.get("blocks." + k,
                                                   0.0) + float(v)
            self.outer, self.v_outer, outer_sq = self._outer_update(
                self.outer, self.v_outer, g_scale, g_head, x_ids, dx)
        pairs = x_ids.size * self.m["moe_top_k"] * len(self.layers)
        self.held_shares.append(on_held / max(pairs, 1))
        if self.first_grad_sq is None:
            sq.update({k: float(v) for k, v in outer_sq.items()})
            self.first_grad_sq = sq
        return float(nll) + self.alpha * aux_sum

    def first_grad_norms(self):
        return {k: math.sqrt(v) for k, v in self.first_grad_sq.items()}

    def change_norms(self, init_leaf):
        """Per-leaf norm of (parameters now - parameters at the start);
        ``init_leaf(name)`` gives a leaf's starting value."""
        diff_sq = jax.jit(lambda a, b: _sq(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))
        out = {k: math.sqrt(float(diff_sq(self.outer[k], init_leaf(k))))
               for k in OUTER}
        for k in BLOCK:
            p0 = init_leaf("blocks." + k)
            out["blocks." + k] = math.sqrt(sum(
                float(diff_sq(lp[k], p0[i]))
                for i, lp in enumerate(self.layers)))
        return out
