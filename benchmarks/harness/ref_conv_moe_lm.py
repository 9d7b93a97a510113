"""Plain reference of the convolution-and-attention, routed-experts LM the
benchmark's LFM2-8B-A1B-sized configuration runs.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no sort, no cache.  It imports nothing of the program and is handed
the weights the benchmark made.  A layer on the residual stream ``x`` [T, E]
(pre-norm, two residuals, RMSNorm ``x rsqrt(mean(x^2) + eps) g`` with the
configuration's ``norm_eps``, no biases anywhere; the embedding unscaled, a
final RMSNorm, the head tied to the embedding):

* ``h = rms1(x)``; a **convolution layer**: ``[b, c, u] = h W_in`` (three
  chunks of ``E``, in that order), ``v = b u``, ``z_t = sum_{j < K} w[j]
  v_{t - K + 1 + j}`` (``v`` zero before the start: a depthwise causal
  convolution), ``x' = x + (c z) W_out``; an **attention layer**: ``q, k, v
  = h W_qkv`` as ``H`` / ``KV`` / ``KV`` heads of ``D``; ``q <- rms(q)
  g_q``, ``k <- rms(k) g_k`` over each head's ``D``; rotary on both
  (rotate-half over all ``D``, positions 0 .. T - 1); causal softmax at
  ``1 / sqrt(D)``, each key/value head serving ``H / KV`` query heads
  (`ref_swa_moe_lm.attention`); ``x' = x + a W_o``;
* ``g = rms2(x')``; a **dense layer**: ``x' + (silu(g W_gate) (g W_up))
  W_down``; an **expert layer**: ``s = sigmoid(g W_r)`` over all ``n``
  experts in float32; the experts of a token are the ``k`` largest of ``s +
  bias`` (the bias chooses and does nothing else); their weights ``s_e /
  (sum of the chosen s + 1e-6)``; ``y = sum over the slots whose expert is
  *held* of w_e SwiGLU_e(g)``.  The experts are a plain loop over the held
  ones, each applied to every token and weighted by the router's weight for
  it (zero where the token did not choose it).  **What an absent expert
  would have added is left out**, as in the program: the configuration is
  one chip's share of an expert-parallel layer.  No balance term.

The loss is the mean cross-entropy.  Training follows the configuration's
optimizer: SGD with momentum on parameters and momentum *stored* in the
model's type, ``v <- m v + g``, ``p <- p - lr v``, each rounded once to
storage; **the expert bias takes no gradient**: after each step it moves by
``moe_bias_rate * sign(mean(c) - c_e)``, ``c_e`` the step's (token, slot)
pairs routed to expert ``e`` of that layer, over all ``n``.  The state is
kept a layer at a time and each layer is updated as soon as its gradient is
known.  ``operand`` swaps in the control's rounding on the operands of every
matrix product but the router's (which the configuration states in
float32), and ``fault`` plants a fault: ``half_batch``, ``softmax_router``
(softmax scores), ``no_expert_bias`` (the experts chosen on ``s`` alone and
the bias never moved), ``no_qk_norm``, ``conv_shifted`` (every tap a step
later).  ``held_shares`` records, a step, the share of (token, slot) pairs
that landed on held experts.  The first gradient's norms leave the bias
out: it has none, so its change after three steps is compared as any
leaf's.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .ref_mla_moe_lm import gated_mlp, sgd_momentum
from .ref_swa_moe_lm import _LOSS_BLOCK, _block_of, attention, rope, \
    rope_tables
from .ref_transformer import OPERANDS, _f32, _sq, rmsnorm

COMMON = ("ln1_scale", "ln2_scale")
OWN = {"conv": ("in_proj", "conv_w", "out_proj"),
       "attention": ("wqkv", "wo", "q_norm_scale", "k_norm_scale"),
       "dense": ("w_gate", "w_up", "w_down"),
       "moe": ("gate", "moe_gate", "moe_up", "moe_down", "expert_bias")}
PREFIX = {"conv": "sconv.", "attention": "attn.", "dense": "dense.",
          "moe": "moe."}
OUTER = ("embed", "final_ln_scale")
BIAS = "moe.expert_bias"
FAULTS = ("half_batch", "softmax_router", "no_expert_bias", "no_qk_norm",
          "conv_shifted")


def conv_mixer(lp, h, q_, fault=None):
    t = h.shape[1]
    b, c, u = jnp.split(jnp.einsum("bte,ef->btf", q_(h), q_(lp["in_proj"])),
                        3, axis=-1)
    v = b * u
    w = lp["conv_w"]
    late = 1 if fault == "conv_shifted" else 0
    kk = w.shape[0] + late
    vp = jnp.pad(v, ((0, 0), (kk - 1, 0), (0, 0)))
    # tap j reads the step K - 1 - j back
    z = sum(vp[:, j:j + t] * w[j] for j in range(w.shape[0]))
    return jnp.einsum("bte,ef->btf", q_(c * z), q_(lp["out_proj"]))


def attention_mixer(lp, h, m, q_, fault=None):
    b, t, _ = h.shape
    heads, kv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    qkv = jnp.einsum("bte,ef->btf", q_(h), q_(lp["wqkv"]))
    q, k, v = jnp.split(qkv, [heads * d, (heads + kv) * d], axis=-1)
    q = q.reshape(b, t, heads, d)
    k = k.reshape(b, t, kv, d)
    v = v.reshape(b, t, kv, d)
    if fault != "no_qk_norm":
        q = rmsnorm(q, lp["q_norm_scale"], eps)
        k = rmsnorm(k, lp["k_norm_scale"], eps)
    cos, sin = rope_tables(m, t)
    q = jax.vmap(lambda a: rope(a, cos, sin))(q)
    k = jax.vmap(lambda a: rope(a, cos, sin))(k)
    attn = jax.lax.map(lambda row: attention(*row, 0, q_), (q, k, v))
    return jnp.einsum("btf,fe->bte", q_(attn), q_(lp["wo"]))


def route(lp, g, m, fault=None):
    """Float32 whatever the operand: ``(weights [B, T, k], experts [B, T,
    k], load [n])``."""
    n, k = m["n_experts"], m["moe_top_k"]
    logits = jnp.einsum("bte,en->btn", g, lp["gate"])
    s = (jax.nn.softmax(logits, axis=-1) if fault == "softmax_router"
         else jax.nn.sigmoid(logits))
    choose = s if fault == "no_expert_bias" else s + lp["expert_bias"]
    _, experts = jax.lax.top_k(choose, k)
    weights = jnp.take_along_axis(s, experts, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    load = jnp.sum(jax.nn.one_hot(experts, n, dtype=jnp.float32),
                   axis=(0, 1, 2))
    return weights, experts, jax.lax.stop_gradient(load)


def held_experts(lp, g, weights, experts, held, q_):
    """``sum over the held experts of w_e(t) SwiGLU_e(g_t)``: a plain
    loop."""
    @jax.checkpoint
    def one(y, xs):
        w_gate, w_up, w_down, expert = xs
        # the router's weight of this expert for each token, 0 where the
        # token did not choose it
        w = jnp.sum(jnp.where(experts == expert, weights, 0.0), axis=-1)
        return y + w[..., None] * gated_mlp(g, w_gate, w_up, w_down, q_), None

    return jax.lax.scan(one, jnp.zeros_like(g),
                        (lp["moe_gate"], lp["moe_up"], lp["moe_down"],
                         jnp.asarray(held, jnp.int32)))[0]


def layer(lp, x, kind, m, operand=None, fault=None):
    """One layer on x [B, T, E] -> (x, load [n] or 0, pairs on held).
    ``kind`` is ``(mixer, mlp)``."""
    q_ = OPERANDS[operand]
    mixer, mlp = kind
    eps = m["norm_eps"]
    h = rmsnorm(x, lp["ln1_scale"], eps)
    x = x + (conv_mixer(lp, h, q_, fault) if mixer == "conv"
             else attention_mixer(lp, h, m, q_, fault))
    g = rmsnorm(x, lp["ln2_scale"], eps)
    if mlp == "dense":
        return (x + gated_mlp(g, lp["w_gate"], lp["w_up"], lp["w_down"], q_),
                jnp.float32(0.0), jnp.float32(0.0))
    held = list(m["experts_held"]) or list(range(m["n_experts"]))
    weights, experts, load = route(lp, g, m, fault)
    y = held_experts(lp, g, weights, experts, held, q_)
    on_held = jnp.sum(jnp.isin(experts, jnp.asarray(held, jnp.int32)))
    return x + y, load, on_held.astype(jnp.float32)


def head_loss_sum(hp, x, targets, eps, operand=None):
    """Sum over all tokens of the cross-entropy of the tied head; x [B, T,
    E], a block of rows at a time."""
    q_ = OPERANDS[operand]
    e = x.shape[-1]
    rows = x.reshape(-1, e)
    rb = _block_of(rows.shape[0], _LOSS_BLOCK)

    @jax.checkpoint
    def block(args):
        xr, tr = args
        h = rmsnorm(xr, hp["final_ln_scale"], eps)
        logits = jnp.einsum("ne,ve->nv", q_(h), q_(hp["embed"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)
    return jnp.sum(jax.lax.map(block, (rows.reshape(-1, rb, e),
                                       targets.reshape(-1, rb))))


def kinds_of(m):
    """[(mixer, mlp)] a layer."""
    return list(zip(m["layer_types"], m["mlp_types"]))


def layer_leaves(params, kinds, i):
    """Layer ``i``'s leaves out of the flat dict: the common stack by the
    layer, each kind's own by the layer's place among its kind."""
    lp = {k: params["blocks." + k][i] for k in COMMON}
    for kind in kinds[i]:
        at = [kk[kinds[i].index(kind)] for kk in kinds[:i]].count(kind)
        lp.update({k: params[PREFIX[kind] + k][at] for k in OWN[kind]})
    return lp


def bias_step(bias, load, rate):
    """``bias + rate sign(mean(load) - load)``."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def forward_loss(m, params, tokens, operand=None, fault=None):
    """The whole loss in one piece (tests at toy sizes): ``params`` the flat
    dict, ``tokens`` [B, T + 1].  Returns ``(loss, [load of each expert
    layer])``."""
    kinds = kinds_of(m)
    x = params["embed"][tokens[:, :-1]].astype(jnp.float32)
    loads = []
    for i, kind in enumerate(kinds):
        x, load, _ = layer(_f32(layer_leaves(params, kinds, i)), x, kind, m,
                           operand, fault)
        if kind[1] == "moe":
            loads.append(load)
    nll = head_loss_sum(_f32({k: params[k] for k in OUTER}), x,
                        tokens[:, 1:], m["norm_eps"], operand)
    return nll / tokens[:, 1:].size, loads


class TrainReference:
    """The reference trainer.  ``params`` is the flat dict the benchmark
    made; it is split into layers (and copied) here."""

    def __init__(self, model, params, optimizer, device=None, operand=None,
                 fault=None):
        assert fault is None or fault in FAULTS, \
            "no fault %r: this family plants %s" % (fault, ", ".join(FAULTS))
        self.m = m = model
        self.fault = fault
        self.kinds = kinds = kinds_of(m)
        self.rate = 0.0 if fault == "no_expert_bias" else float(
            m["moe_bias_rate"])
        self.home = device or jax.devices()[0]
        put = lambda a: jax.device_put(a, self.home)
        self.layers = [{k: put(v) for k, v in
                        layer_leaves(params, kinds, i).items()}
                       for i in range(len(kinds))]
        # a copy: the updates donate these, and the caller keeps its own
        self.outer = {k: put(jnp.copy(params[k])) for k in OUTER}
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        self.v_layers = [zeros(lp) for lp in self.layers]
        self.v_outer = zeros(self.outer)
        self.first_grad_sq = None
        self.held_shares = []
        # the jitted functions below hold no ``self``: a trainer that is
        # dropped frees its state at once, with no cycle to collect
        update = functools.partial(sgd_momentum, float(optimizer["momentum"]),
                                   float(optimizer["lr"]))
        eps, rate = m["norm_eps"], self.rate

        def fwd(kind):
            return jax.jit(lambda lp, x: layer(_f32(lp), x, kind, m,
                                               operand, fault))

        def bwd_update(kind):
            def fn(lp, lv, x, dx, load):
                bias = lp.pop("expert_bias", None)

                def f(p, x_):
                    full = dict(p) if bias is None else dict(
                        p, expert_bias=bias.astype(jnp.float32))
                    return layer(full, x_, kind, m, operand, fault)[0]
                _, vjp = jax.vjp(f, _f32(lp), x)
                g, dx_in = vjp(dx)
                new_p, new_v = update(lp, lv, g)
                if bias is not None:
                    new_p["expert_bias"] = bias_step(bias, load, rate)
                    new_v["expert_bias"] = jnp.zeros_like(bias)
                return dx_in, new_p, new_v, {k: _sq(a) for k, a in g.items()}
            return jax.jit(fn, donate_argnums=(0, 1))

        def head(hp, x, targets, n_tokens):
            def loss_fn(hp32, x_):
                return head_loss_sum(hp32, x_, targets, eps,
                                     operand) / n_tokens
            loss, vjp = jax.vjp(loss_fn, _f32(hp), x)
            g_hp, dx = vjp(jnp.float32(1.0))
            return loss, dx, g_hp["final_ln_scale"], g_hp["embed"]

        def outer_update(outer, v_outer, g_scale, g_head, tokens, dx0):
            # the tied embedding: the head's gradient and the lookup's
            g = {"final_ln_scale": g_scale,
                 "embed": g_head.at[tokens].add(dx0)}
            new_p, new_v = update(outer, v_outer, g)
            return new_p, new_v, {k: _sq(a) for k, a in g.items()}

        self._fwd = {k: fwd(k) for k in set(kinds)}
        self._bwd = {k: bwd_update(k) for k in set(kinds)}
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
            xs, loads, on_held = [], [], 0.0
            for kind, lp in zip(self.kinds, self.layers):
                xs.append(x)
                x, load, held = self._fwd[kind](lp, x)
                loads.append(load)
                on_held += float(held)
            nll, dx, g_scale, g_head = self._head(
                self.outer, x, y_ids, int(y_ids.size))
            layer_sq = []
            for i in reversed(range(len(self.layers))):
                kind = self.kinds[i]
                dx, self.layers[i], self.v_layers[i], gsq = self._bwd[kind](
                    self.layers[i], self.v_layers[i], xs[i], dx, loads[i])
                xs[i] = None
                layer_sq.append((kind, gsq))
            self.outer, self.v_outer, outer_sq = self._outer_update(
                self.outer, self.v_outer, g_scale, g_head, x_ids, dx)
        pairs = (x_ids.size * self.m["moe_top_k"]
                 * [k[1] for k in self.kinds].count("moe"))
        self.held_shares.append(on_held / max(pairs, 1))
        if self.first_grad_sq is None:
            sq = {k: float(v) for k, v in outer_sq.items()}
            for kind, gsq in layer_sq:
                for k, v in gsq.items():
                    name = leaf_name(kind, k)
                    if name != BIAS:
                        sq[name] = sq.get(name, 0.0) + float(v)
            self.first_grad_sq = sq
        return float(nll)

    def first_grad_norms(self):
        return {k: math.sqrt(v) for k, v in self.first_grad_sq.items()}

    def change_norms(self, init_leaf):
        """Per-leaf norm of (parameters now - parameters at the start);
        ``init_leaf(name)`` gives a leaf's starting value."""
        diff_sq = jax.jit(lambda a, b: _sq(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))
        out = {k: math.sqrt(float(diff_sq(self.outer[k], init_leaf(k))))
               for k in OUTER}
        total, start = {}, {}
        for i, (kind, lp) in enumerate(zip(self.kinds, self.layers)):
            for k in lp:
                name = leaf_name(kind, k)
                if name not in start:
                    start[name] = (init_leaf(name), 0)
                p0, at = start[name]
                row = i if name.startswith("blocks.") else at
                total[name] = total.get(name, 0.0) + float(
                    diff_sq(lp[k], p0[row]))
                start[name] = (p0, at + 1)
        out.update({k: math.sqrt(v) for k, v in total.items()})
        return out


def leaf_name(kind, k):
    """The flat name of leaf ``k`` of a layer of ``kind`` (mixer, mlp)."""
    if k in COMMON:
        return "blocks." + k
    return next(PREFIX[part] + k for part in kind if k in OWN[part])
