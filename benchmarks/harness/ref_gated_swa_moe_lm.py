"""Plain reference of the gated window-and-full-attention, routed-experts LM
the benchmark's Laguna-S-2.1-sized configuration runs.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no sort, no cache.  It imports nothing of the program and is handed
the weights the benchmark made.  A layer ``l`` on the residual stream ``x``
[T, E] (pre-norm, two residuals, RMSNorm eps 1e-6, no biases anywhere; the
embedding unscaled, a final RMSNorm, the head untied):

* ``h = rms1(x)``; ``q, k, v = h W_qkv`` as ``H_l`` / ``KV`` / ``KV`` heads
  of ``D``, ``H_l`` the layer's own query heads (``attn_heads``);
* the layer's rotary term (its ``attn_rope`` entry, a setting) on q and k:
  the leading ``rot = D fraction`` columns of a head turn by rotate-half
  within themselves (column ``i`` pairs with ``i + rot / 2``; positions 0
  .. T - 1), the other ``D - rot`` pass through; ``f_i = theta^(-2 i /
  rot)``, and with ``factor`` > 1 YaRN: between the pairs ``low =
  floor(pair(beta_fast))`` and ``high = ceil(pair(beta_slow))``, ``pair(n) =
  rot ln(L / (2 pi n)) / (2 ln theta)``, the frequency blends linearly from
  ``f_i`` to ``f_i / factor``, and cos and sin times YaRN's temperature
  ``0.1 ln(factor) + 1``;
* ``a_t = softmax_j(q_t . k_j / sqrt(D)) v_j`` over ``j <= t``, or on a
  layer whose ``attn_windows`` entry is ``W`` > 0 over ``0 <= t - j < W``
  (the mask written out; `ref_swa_moe_lm.attention`), each key/value head
  serving ``H_l / KV`` query heads;
* **the per-head gate**: head ``j`` of ``a_t`` times ``sigmoid(h_t .
  W_g[:, j])`` (``W_g`` [E, H_l], float32); ``x' = x + a W_o``;
* ``g = rms2(x')``; a **dense layer**: ``x' + (silu(g W_gate) (g W_up))
  W_down``; an **expert layer**: ``s = softmax(g W_r)`` over all ``n``
  experts in float32, the ``k`` largest, their weights renormalised to sum
  to one and **times** ``moe_routed_scale``; ``y = x' + sum over the slots
  whose expert is *held* of w SwiGLU_e(g) + SwiGLU_shared(g)``, the shared
  expert unweighted.  The experts are a plain loop over the held ones, each
  applied to every token and weighted by the router's weight for it (zero
  where the token did not choose it).  **What an absent expert would have
  added is left out**, as in the program: the configuration is one chip's
  share of an expert-parallel layer.  The balance term of a sequence is
  ``sum_i f_i P_i`` over all ``n`` experts (`ref_mla_moe_lm.route`); the
  loss is the mean cross-entropy plus ``moe_aux_weight`` times the sum over
  the expert layers of the sequences' mean balance term.

Training follows the configuration's optimizer: SGD with momentum on
parameters and momentum *stored* in the model's type (`ref_mla_moe_lm.
sgd_momentum`).  The state is kept a layer at a time and each layer is
updated as soon as its gradient is known.  ``operand`` swaps in the
control's rounding on the operands of every matrix product but the
router's (which the configuration states in float32), and ``fault`` plants
a fault, so the same code gives the readings the limits are set from:
``half_batch`` (half the step's tokens), ``no_gate`` (no output gate),
``whole_rotary`` (every column of a head turns), ``unscaled_routing`` (the
routed sum not scaled).  ``held_shares`` records, a step, the share of
(token, slot) pairs that landed on held experts.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .ref_conv_moe_lm import held_experts
from .ref_mla_moe_lm import gated_mlp, route, sgd_momentum, yarn_range
from .ref_swa_moe_lm import attention, head_loss_sum, rope
from .ref_transformer import OPERANDS, _f32, _sq, rmsnorm

COMMON = ("ln1_scale", "ln2_scale")
ATTN = ("wqkv", "wo", "head_gate")
OWN = {"dense": ("w_gate", "w_up", "w_down"),
       "moe": ("gate", "moe_gate", "moe_up", "moe_down", "shared_gate",
               "shared_up", "shared_down")}
OUTER = ("embed", "final_ln_scale", "unembed")
FAULTS = ("half_batch", "no_gate", "whole_rotary", "unscaled_routing")


def rope_tables(setting, d, t, fault=None):
    """cos, sin [t, rot] float32 of a rotary setting (a dict) over heads of
    ``d``: pair i's angle in columns i and i + rot / 2."""
    fraction = 1.0 if fault == "whole_rotary" else setting.get("fraction", 1)
    rot = int(d * fraction)
    theta, factor = setting["theta"], setting.get("factor", 1)
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    scale = 1.0
    if factor > 1:
        low, high = yarn_range(rot, theta, setting["orig_len"],
                               setting["beta_fast"], setting["beta_slow"])
        ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scale = 0.1 * math.log(factor) + 1.0
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    angle = np.concatenate([angle, angle], axis=-1)
    return (jnp.asarray(np.cos(angle) * scale, jnp.float32),
            jnp.asarray(np.sin(angle) * scale, jnp.float32))


def turn(x, cos, sin):
    """x [T, heads, D]: the leading ``cos.shape[-1]`` columns turned."""
    rot = cos.shape[-1]
    return jnp.concatenate([rope(x[..., :rot], cos, sin), x[..., rot:]],
                           axis=-1)


def kinds_of(m):
    """A layer's kind: (query heads, window, rotary setting as items, MLP
    kind) -- what its reference function depends on."""
    heads = [h or m["n_heads"] for h in m["attn_heads"]]
    return [(heads[i], int(m["attn_windows"][i]),
             tuple(sorted(m["attn_rope"][i].items())), m["mlp_types"][i])
            for i in range(m["n_layers"])]


def layer(lp, x, kind, m, operand=None, fault=None):
    """One layer on x [B, T, E] -> (x, balance term, pairs on held)."""
    q_ = OPERANDS[operand]
    heads, window, setting, mlp = kind
    b, t, _ = x.shape
    kv, d = m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    h = rmsnorm(x, lp["ln1_scale"], eps)
    qkv = jnp.einsum("bte,ef->btf", q_(h), q_(lp["wqkv"]))
    q, k, v = jnp.split(qkv, [heads * d, (heads + kv) * d], axis=-1)
    q = q.reshape(b, t, heads, d)
    k = k.reshape(b, t, kv, d)
    v = v.reshape(b, t, kv, d)
    cos, sin = rope_tables(dict(setting), d, t, fault)
    q = jax.vmap(lambda a: turn(a, cos, sin))(q)
    k = jax.vmap(lambda a: turn(a, cos, sin))(k)
    attn = jax.lax.map(lambda row: attention(*row, window, q_), (q, k, v))
    if fault != "no_gate":
        gate = jax.nn.sigmoid(jnp.einsum("bte,eh->bth", q_(h),
                                         q_(lp["head_gate"])))
        attn = (attn.reshape(b, t, heads, d) * gate[..., None]
                ).reshape(b, t, heads * d)
    x = x + jnp.einsum("btf,fe->bte", q_(attn), q_(lp["wo"]))
    g = rmsnorm(x, lp["ln2_scale"], eps)
    if mlp == "dense":
        return (x + gated_mlp(g, lp["w_gate"], lp["w_up"], lp["w_down"], q_),
                jnp.float32(0.0), jnp.float32(0.0))
    held = list(m["experts_held"]) or list(range(m["n_experts"]))
    weights, experts, aux = route(lp, g, m)
    if fault != "unscaled_routing":
        weights = weights * m["moe_routed_scale"]
    y = held_experts(lp, g, weights, experts, held, q_)
    y = y + gated_mlp(g, lp["shared_gate"], lp["shared_up"],
                      lp["shared_down"], q_)
    on_held = jnp.sum(jnp.isin(experts, jnp.asarray(held, jnp.int32)))
    return x + y, aux, on_held.astype(jnp.float32)


def leaf_name(kind, k):
    """The flat name of leaf ``k`` of a layer of ``kind``."""
    if k in COMMON:
        return "blocks." + k
    if k in ATTN:
        return "attn%d.%s" % (kind[0], k)
    return "%s.%s" % (kind[3], k)


def layer_leaves(params, kinds, i):
    """Layer ``i``'s leaves out of the flat dict: the common stack by the
    layer, its head count's and its MLP kind's stacks by the layer's place
    among the layers of that stack."""
    lp = {k: params["blocks." + k][i] for k in COMMON}
    heads, mlp = kinds[i][0], kinds[i][3]
    at = [kk[0] for kk in kinds[:i]].count(heads)
    lp.update({k: params[leaf_name(kinds[i], k)][at] for k in ATTN})
    at = [kk[3] for kk in kinds[:i]].count(mlp)
    lp.update({k: params[leaf_name(kinds[i], k)][at] for k in OWN[mlp]})
    return lp


def forward_loss(m, params, tokens, operand=None, fault=None):
    """The whole loss in one piece (tests at toy sizes): ``params`` the flat
    dict, ``tokens`` [B, T + 1]."""
    kinds = kinds_of(m)
    x = params["embed"][tokens[:, :-1]].astype(jnp.float32)
    aux = 0.0
    for i, kind in enumerate(kinds):
        x, a, _ = layer(_f32(layer_leaves(params, kinds, i)), x, kind, m,
                        operand, fault)
        aux = aux + a
    nll = head_loss_sum(_f32({k: params[k] for k in OUTER}), x,
                        tokens[:, 1:], operand) / tokens[:, 1:].size
    return nll + m["moe_aux_weight"] * aux


class TrainReference:
    """The reference trainer.  ``params`` is the flat dict the benchmark
    made; it is split into layers (and copied) here."""

    def __init__(self, model, params, optimizer, device=None, operand=None,
                 fault=None):
        assert fault is None or fault in FAULTS, \
            "no fault %r: this family plants %s" % (fault, ", ".join(FAULTS))
        self.m = m = model
        self.fault = fault
        self.alpha = float(m["moe_aux_weight"])
        self.kinds = kinds = kinds_of(m)
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
        alpha = jnp.float32(self.alpha)
        update = functools.partial(sgd_momentum, float(optimizer["momentum"]),
                                   float(optimizer["lr"]))

        def fwd(kind):
            return jax.jit(lambda lp, x: layer(_f32(lp), x, kind, m,
                                               operand, fault))

        def bwd_update(kind):
            def fn(lp, lv, x, dx):
                _, vjp = jax.vjp(lambda p, x_: layer(p, x_, kind, m,
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
            xs, aux_sum, on_held = [], 0.0, 0.0
            for kind, lp in zip(self.kinds, self.layers):
                xs.append(x)
                x, aux, held = self._fwd[kind](lp, x)
                aux_sum += float(aux)
                on_held += float(held)
            nll, dx, g_scale, g_head = self._head(
                self.outer, x, y_ids, int(y_ids.size))
            layer_sq = []
            for i in reversed(range(len(self.layers))):
                dx, self.layers[i], self.v_layers[i], gsq = \
                    self._bwd[self.kinds[i]](self.layers[i], self.v_layers[i],
                                             xs[i], dx)
                xs[i] = None
                layer_sq.append((self.kinds[i], gsq))
            self.outer, self.v_outer, outer_sq = self._outer_update(
                self.outer, self.v_outer, g_scale, g_head, x_ids, dx)
        pairs = (x_ids.size * self.m["moe_top_k"]
                 * [k[3] for k in self.kinds].count("moe"))
        self.held_shares.append(on_held / max(pairs, 1))
        if self.first_grad_sq is None:
            sq = {k: float(v) for k, v in outer_sq.items()}
            for kind, gsq in layer_sq:
                for k, v in gsq.items():
                    name = leaf_name(kind, k)
                    sq[name] = sq.get(name, 0.0) + float(v)
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
