"""Plain reference of the latent-attention, routed-experts LM the benchmark's
DeepSeek-V2-Lite-sized configuration runs.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no sort, no cache.  It imports nothing of the program and is handed
the weights the benchmark made.  Every layer is pre-norm with two residuals
(RMSNorm, eps 1e-6, no biases): ``x += mla(norm1(x))``, ``x +=
mlp(norm2(x))``; the embedding is unscaled, the head untied.

* latent attention (MLA, the query not compressed): ``q = h wq`` as ``H``
  heads of ``dn + dr``; ``[c, k_pe] = h wkv_a`` (``r`` and ``dr`` wide: one
  rotary key head for all query heads); ``c <- rms(c) g``; ``[k_nope, v] = c
  wkv_b`` as ``H`` heads of ``dn + dv``; rotary on ``q_pe`` and ``k_pe``
  (YaRN frequencies, rotate-half layout: column ``i`` of a rotary part pairs
  with column ``i + dr / 2``); causal softmax of ``[q_nope, q_pe] . [k_nope,
  k_pe]^T scale`` with ``scale = (dn + dr)^-1/2 mscale^2``; ``P v``; ``wo``;
* YaRN: ``f_i = base^(-2 i / dr)``; between the pairs ``low = floor(pair(
  beta_fast))`` and ``high = ceil(pair(beta_slow))``, ``pair(n) = dr ln(L /
  (2 pi n)) / (2 ln base)``, the frequency blends linearly from ``f_i`` to
  ``f_i / factor``; ``mscale(m) = 0.1 m ln(factor) + 1``; cos and sin carry
  ``mscale(rope_mscale) / mscale(rope_mscale_all_dim)`` (1 where they are
  equal) and the scores ``mscale(rope_mscale_all_dim)^2``;
* dense layers: ``down(silu(gate h) * up h)``;
* expert layers: ``s = softmax(h Wg)`` over all ``n`` experts in float32;
  the ``k`` largest (greedy), weights as they are (no renormalising unless
  the model says so; the family's ``routed_scaling_factor`` is 1 here);
  ``y = sum over the slots whose
  expert is *held* of w E_i(h) + S(h)``, ``E_i`` and the shared expert ``S``
  of the gated form.  The experts are a plain loop over the held ones, each
  applied to every token and weighted by the router's weight for it (zero
  where the token did not choose it).  **What an absent expert would have
  added is left out**, as in the program: the configuration is one chip's
  share of an expert-parallel layer.  The balance term of a sequence is
  ``sum_i f_i P_i``, ``f_i`` = slots routed to ``i`` times ``n / (k T)`` (a
  count), ``P_i`` the sequence's mean of ``s_i``, over all ``n`` experts;
  the loss is the mean cross-entropy plus ``moe_aux_weight`` times the sum
  over the expert layers of the sequences' mean balance term.

Training follows the configuration's optimizer: SGD with momentum on
parameters and momentum *stored* in the model's type: ``v <- m v + g``, ``p
<- p - lr v``, each rounded once to storage.  The state is kept a layer at a
time and each layer is updated as soon as its gradient is known.
``operand`` swaps in the control's rounding on the operands of every matrix
product but the router's (which the configuration states in float32), and
``fault`` plants a training fault, so the same code gives the readings the
limits are set from.  ``held_shares`` records, a step, the share of (token,
slot) pairs that landed on held experts.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .ref_transformer import OPERANDS, _f32, _sq, rmsnorm

COMMON = ("ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_norm_scale", "wkv_b",
          "wo")
OWN = {"dense": ("w_gate", "w_up", "w_down"),
       "moe": ("gate", "moe_gate", "moe_up", "moe_down", "shared_gate",
               "shared_up", "shared_down")}
PREFIX = {"dense": "dense.", "moe": "moe."}
OUTER = ("embed", "final_ln_scale", "unembed")


def silu(x):
    return x / (1.0 + jnp.exp(-x))


# -- YaRN -------------------------------------------------------------------
def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_range(dim, base, orig_len, beta_fast, beta_slow):
    def pair(turns):
        return dim * math.log(orig_len / (2 * math.pi * turns)) / (
            2 * math.log(base))
    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def yarn_inv_freq(m):
    dim, base, factor = (m["qk_rope_head_dim"], m["rope_theta"],
                         m["rope_factor"])
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra
    low, high = yarn_range(dim, base, m["rope_orig_len"],
                           m["rope_beta_fast"], m["rope_beta_slow"])
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def rope_tables(m, t):
    angle = np.arange(t, dtype=np.float64)[:, None] * yarn_inv_freq(m)[None]
    angle = np.concatenate([angle, angle], axis=-1)
    c = (yarn_mscale(m["rope_factor"], m["rope_mscale"])
         / yarn_mscale(m["rope_factor"], m["rope_mscale_all_dim"]))
    return (jnp.asarray(np.cos(angle) * c, jnp.float32),
            jnp.asarray(np.sin(angle) * c, jnp.float32))


def softmax_scale(m):
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if m["rope_mscale_all_dim"]:
        scale *= yarn_mscale(m["rope_factor"], m["rope_mscale_all_dim"]) ** 2
    return scale


def rope(x, cos, sin):
    """x [..., T, heads, dr]; cos, sin [T, dr]."""
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos[:, None, :] + jnp.concatenate([-b, a], -1) * sin[:, None, :]


# -- layers -----------------------------------------------------------------
def mla_mixer(lp, h, m, q_):
    b, t, e = h.shape
    heads = m["n_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    q = jnp.einsum("bte,ef->btf", q_(h), q_(lp["wq"])).reshape(
        b, t, heads, dn + dr)
    ckv = jnp.einsum("bte,ef->btf", q_(h), q_(lp["wkv_a"]))
    c, k_pe = ckv[..., :r], ckv[..., r:]
    c = rmsnorm(c, lp["kv_norm_scale"])
    kv = jnp.einsum("btr,rf->btf", q_(c), q_(lp["wkv_b"])).reshape(
        b, t, heads, dn + dv)
    cos, sin = rope_tables(m, t)
    scale = softmax_scale(m)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def attend(row):
        qr, kvr, k_per = row                  # [T, H, .], [T, H, .], [T, dr]
        q_full = jnp.concatenate(
            [qr[..., :dn], rope(qr[..., dn:], cos, sin)], axis=-1)
        k_rot = rope(k_per[:, None, :], cos, sin)
        k_full = jnp.concatenate(
            [kvr[..., :dn], jnp.broadcast_to(k_rot, (t, heads, dr))], axis=-1)
        s = jnp.einsum("qhd,khd->hqk", q_(q_full), q_(k_full)) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q_(p), q_(kvr[..., dn:])
                          ).reshape(t, heads * dv)

    attn = jax.lax.map(attend, (q, kv, k_pe))
    return jnp.einsum("btf,fe->bte", q_(attn), q_(lp["wo"]))


def gated_mlp(h, w_gate, w_up, w_down, q_):
    gate = jnp.einsum("...e,ef->...f", q_(h), q_(w_gate))
    up = jnp.einsum("...e,ef->...f", q_(h), q_(w_up))
    return jnp.einsum("...f,fe->...e", q_(silu(gate) * up), q_(w_down))


def route(lp, h, m):
    """Float32 whatever the operand: ``(weights [B, T, k], experts [B, T,
    k], the sequences' mean balance term)``."""
    n, k = m["n_experts"], m["moe_top_k"]
    t = h.shape[1]
    s = jax.nn.softmax(jnp.einsum("bte,en->btn", h, lp["gate"]), axis=-1)
    weights, experts = jax.lax.top_k(s, k)
    if m.get("moe_renormalize", True) and k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    chosen = jnp.sum(jax.nn.one_hot(experts, n, dtype=jnp.float32), axis=2)
    f = jax.lax.stop_gradient(jnp.sum(chosen, axis=1)) * (n / (k * t))
    aux = jnp.mean(jnp.sum(f * jnp.mean(s, axis=1), axis=-1))
    return weights, experts, aux


def expert_mlp(lp, h, m, q_):
    """The held routed experts' part plus the shared expert; also the
    balance term and the number of (token, slot) pairs on held experts."""
    held = list(m["experts_held"]) or list(range(m["n_experts"]))
    weights, experts, aux = route(lp, h, m)

    @jax.checkpoint
    def one(y, xs):
        w_gate, w_up, w_down, expert = xs
        # the router's weight of this expert for each token, 0 where the
        # token did not choose it
        w = jnp.sum(jnp.where(experts == expert, weights, 0.0), axis=-1)
        return y + w[..., None] * gated_mlp(h, w_gate, w_up, w_down, q_), None

    y = jax.lax.scan(one, jnp.zeros_like(h),
                     (lp["moe_gate"], lp["moe_up"], lp["moe_down"],
                      jnp.asarray(held, jnp.int32)))[0]
    y = y + gated_mlp(h, lp["shared_gate"], lp["shared_up"],
                      lp["shared_down"], q_)
    on_held = jnp.sum(jnp.isin(experts, jnp.asarray(held, jnp.int32)))
    return y, aux, on_held.astype(jnp.float32)


def layer(lp, x, kind, m, operand=None):
    """One layer on x [B, T, E] -> (x, balance term, pairs on held)."""
    q_ = OPERANDS[operand]
    x = x + mla_mixer(lp, rmsnorm(x, lp["ln1_scale"]), m, q_)
    h = rmsnorm(x, lp["ln2_scale"])
    if kind == "dense":
        return (x + gated_mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"], q_),
                jnp.float32(0.0), jnp.float32(0.0))
    y, aux, on_held = expert_mlp(lp, h, m, q_)
    return x + y, aux, on_held


def head_loss_sum(hp, x, targets, operand=None):
    """Sum over all tokens of the cross-entropy; x [B, T, E]."""
    q_ = OPERANDS[operand]

    @jax.checkpoint
    def row(args):
        xr, tr = args
        h = rmsnorm(xr, hp["final_ln_scale"])
        logits = jnp.einsum("ne,ev->nv", q_(h), q_(hp["unembed"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)
    return jnp.sum(jax.lax.map(row, (x, targets)))


def forward_loss(m, params, tokens, operand=None):
    """The whole loss in one piece (tests at toy sizes): ``params`` the flat
    dict, ``tokens`` [B, T + 1]."""
    kinds = list(m["mlp_types"])
    x = params["embed"][tokens[:, :-1]].astype(jnp.float32)
    seen = {"dense": 0, "moe": 0}
    aux = 0.0
    for i, kind in enumerate(kinds):
        lp = {k: params["blocks." + k][i] for k in COMMON}
        lp.update({k: params[PREFIX[kind] + k][seen[kind]]
                   for k in OWN[kind]})
        seen[kind] += 1
        x, a, _ = layer(_f32(lp), x, kind, m, operand)
        aux = aux + a
    n = tokens[:, 1:].size
    nll = head_loss_sum(_f32({k: params[k] for k in OUTER}), x,
                        tokens[:, 1:], operand) / n
    return nll + m.get("moe_aux_weight", 0.01) * aux


def sgd_momentum(mom, lr, p, v, g):
    """``v <- mom v + g``, ``p <- p - lr v``, each leaf rounded once to the
    type it is stored in."""
    new_v = {k: (mom * v[k].astype(jnp.float32) + g[k]).astype(v[k].dtype)
             for k in p}
    new_p = {k: (p[k].astype(jnp.float32)
                 - lr * new_v[k].astype(jnp.float32)).astype(p[k].dtype)
             for k in p}
    return new_p, new_v


class TrainReference:
    """The reference trainer.  ``params`` is the flat dict the benchmark
    made; it is split into layers (and copied) here."""

    def __init__(self, model, params, optimizer, device=None, operand=None,
                 fault=None):
        self.m = m = model
        self.lr = float(optimizer["lr"])
        self.mom = float(optimizer["momentum"])
        self.fault = fault
        self.alpha = float(m.get("moe_aux_weight", 0.01))
        self.kinds = list(m["mlp_types"])
        self.home = device or jax.devices()[0]
        put = lambda a: jax.device_put(a, self.home)
        # a layer's own leaves sit at its index among the layers of its kind
        self.index = []
        seen = {"dense": 0, "moe": 0}
        for kind in self.kinds:
            self.index.append(seen[kind])
            seen[kind] += 1
        self.layers = []
        for i, kind in enumerate(self.kinds):
            lp = {k: put(params["blocks." + k][i]) for k in COMMON}
            lp.update({k: put(params[PREFIX[kind] + k][self.index[i]])
                       for k in OWN[kind]})
            self.layers.append(lp)
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

        def fwd(kind):
            return jax.jit(lambda lp, x: layer(_f32(lp), x, kind, m,
                                               operand))

        def bwd_update(kind):
            def fn(lp, lv, x, dx):
                _, vjp = jax.vjp(lambda p, x_: layer(p, x_, kind, m,
                                                     operand)[:2],
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

        self._fwd = {k: fwd(k) for k in OWN}
        self._bwd = {k: bwd_update(k) for k in OWN}
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
        pairs = x_ids.size * self.m["moe_top_k"] * self.kinds.count("moe")
        self.held_shares.append(on_held / max(pairs, 1))
        if self.first_grad_sq is None:
            sq = {k: float(v) for k, v in outer_sq.items()}
            for kind, gsq in layer_sq:
                for k, v in gsq.items():
                    name = ("blocks." if k in COMMON else PREFIX[kind]) + k
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
        names = {"blocks." + k for k in COMMON}
        names.update(PREFIX[kind] + k for kind in set(self.kinds)
                     for k in OWN[kind])
        for name in sorted(names):
            prefix, k = name.split(".", 1)
            p0 = init_leaf(name)
            total = 0.0
            for i, lp in enumerate(self.layers):
                if k in lp:
                    at = i if prefix == "blocks" else self.index[i]
                    total += float(diff_sq(lp[k], p0[at]))
            out[name] = math.sqrt(total)
        return out
