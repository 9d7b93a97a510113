"""Plain reference of the hybrid state-space LM the benchmark's Jamba-sized
configuration runs (AI21's Jamba: Mamba-1 mixers beside attention).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no chunking, no cache.  It imports nothing of the program and is
handed the weights the benchmark made.  Every layer is pre-norm with two
residuals (RMSNorm, eps 1e-6): ``x += mixer(norm1(x))``, ``x +=
mlp(norm2(x))``; the MLP is ``down(silu(gate(h)) * up(h))`` without biases;
the logits are ``final_norm(x) . embed^T`` (tied); no positional term.

* attention mixer: ``q`` over all query heads, ``k`` and ``v`` over the
  shared key/value heads (one fused matrix ``[E, (H + 2 KV) D]``, split in
  that order), causal softmax of ``q k^T / sqrt(D)``, then ``wo``;
* Mamba-1 mixer: ``[u, z] = in_proj(h)``; ``u = silu(causal depthwise
  conv(u) + b_conv)``; ``[d, B, C] = x_proj(u)``; Jamba's three inner norms
  ``rms(d) g_d``, ``rms(B) g_B``, ``rms(C) g_C``; ``delta = softplus(
  dt_proj(d) + b_dt)``; ``A = -exp(A_log)``; the recurrence ``h_t = exp(
  delta_t A) h_{t-1} + delta_t B_t u_t``, ``y_t = C_t . h_t + D u_t`` as a
  plain ``lax.scan`` over time from ``h = 0``; ``out_proj(y * silu(z))``.

Training follows the configuration's optimizer: SGD with momentum on
parameters and momentum *stored* in each leaf's own type (bfloat16, or
float32 for ``A_log``, ``D``, ``dt_bias``): ``v <- m v + g``, ``p <- p - lr
v``, each rounded once to storage.  The tied matrix's gradient is the sum of
its two uses (lookup and head).  The state is kept a layer at a time and
each layer is updated as soon as its gradient is known, so 1.6 B parameters
fit beside nothing else.  ``operand`` swaps in the control's rounding (on
every product's operands and on the scan's inputs ``u``, ``delta``, ``B``,
``C``), and ``fault`` plants a training fault, so the same code gives the
readings the limits are set from.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

# the benchmark's own: RMSNorm, and the roundings by the name a
# configuration's ``precision.control`` gives (``lax.reduce_precision``, one
# scale a tensor for float8, gradients straight through)
from .ref_transformer import OPERANDS, _f32, _sq, rmsnorm

COMMON = ("ln1_scale", "ln2_scale", "w_gate", "w_up", "w_down")
OWN = {"attention": ("wqkv", "wo"),
       "mamba": ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm_scale",
                 "b_norm_scale", "c_norm_scale", "dt_proj", "dt_bias",
                 "A_log", "D", "out_proj")}
PREFIX = {"attention": "attn.", "mamba": "ssm."}
OUTER = ("embed", "final_ln_scale")


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def attention_mixer(lp, h, m, q_):
    b, t, e = h.shape
    heads, kv = m["n_heads"], m["n_kv_heads"]
    d = e // heads
    qkv = jnp.einsum("bte,ef->btf", q_(h), q_(lp["wqkv"]))
    q, k, v = jnp.split(qkv, [heads * d, (heads + kv) * d], axis=-1)
    mask = jnp.tril(jnp.ones((t, t), bool))
    group = heads // kv

    @jax.checkpoint
    def attend(row):
        qr, kr, vr = row
        qr = qr.reshape(t, kv, group, d)
        kr, vr = kr.reshape(t, kv, d), vr.reshape(t, kv, d)
        s = jnp.einsum("qkgd,skd->kgqs", q_(qr), q_(kr)) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", q_(p), q_(vr)).reshape(t, e)

    attn = jax.lax.map(attend, (q, k, v))
    return jnp.einsum("btf,fe->bte", q_(attn), q_(lp["wo"]))


def selective_scan(u, delta, a, b, c, d):
    """One sequence: u, delta [T, Di]; a [Di, N]; b, c [T, N]; d [Di].
    Step by step from a zero state."""
    @jax.checkpoint
    def step(h, xs):
        ut, dt, bt, ct = xs
        h = jnp.exp(dt[:, None] * a) * h + (dt * ut)[:, None] * bt[None, :]
        return h, h @ ct + d * ut

    h0 = jnp.zeros(a.shape, jnp.float32)
    return jax.lax.scan(step, h0, (u, delta, b, c))[1]


def mamba_mixer(lp, h, m, q_):
    n, r, k = m["ssm_state"], m["ssm_dt_rank"], m["ssm_conv"]
    t = h.shape[1]
    u, z = jnp.split(jnp.einsum("bte,ef->btf", q_(h), q_(lp["in_proj"])), 2,
                     axis=-1)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = lp["conv_b"]
    for i in range(k):                  # channel d sees its own last k steps
        conv = conv + padded[:, i:i + t] * lp["conv_w"][i]
    u = silu(conv)
    dbc = jnp.einsum("btd,df->btf", q_(u), q_(lp["x_proj"]))
    dl, b, c = jnp.split(dbc, [r, r + n], axis=-1)
    dl = rmsnorm(dl, lp["dt_norm_scale"])
    b = rmsnorm(b, lp["b_norm_scale"])
    c = rmsnorm(c, lp["c_norm_scale"])
    delta = softplus(jnp.einsum("btr,rd->btd", q_(dl), q_(lp["dt_proj"]))
                     + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])
    y = jax.lax.map(
        lambda row: selective_scan(row[0], row[1], a, row[2], row[3],
                                   lp["D"]),
        (q_(u), q_(delta), q_(b), q_(c)))
    return jnp.einsum("btd,de->bte", q_(y * silu(z)), q_(lp["out_proj"]))


def layer(lp, x, kind, m, operand=None):
    """One layer on x [B, T, E]."""
    q_ = OPERANDS[operand]
    mixer = attention_mixer if kind == "attention" else mamba_mixer
    x = x + mixer(lp, rmsnorm(x, lp["ln1_scale"]), m, q_)
    h = rmsnorm(x, lp["ln2_scale"])
    gate = jnp.einsum("bte,ef->btf", q_(h), q_(lp["w_gate"]))
    up = jnp.einsum("bte,ef->btf", q_(h), q_(lp["w_up"]))
    return x + jnp.einsum("btf,fe->bte", q_(silu(gate) * up),
                          q_(lp["w_down"]))


def head_loss_sum(hp, x, targets, operand=None):
    """Sum over all tokens of the cross-entropy; x [B, T, E]; the head is
    the embedding matrix."""
    q_ = OPERANDS[operand]

    @jax.checkpoint
    def row(args):
        xr, tr = args
        h = rmsnorm(xr, hp["final_ln_scale"])
        logits = jnp.einsum("ne,ve->nv", q_(h), q_(hp["embed"]))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)
    return jnp.sum(jax.lax.map(row, (x, targets)))


class TrainReference:
    """The reference trainer.  ``params`` is the flat dict the benchmark
    made; it is split into layers (and copied) here."""

    def __init__(self, model, params, optimizer, device=None, operand=None,
                 fault=None):
        self.m = m = model
        self.lr = float(optimizer["lr"])
        self.mom = float(optimizer["momentum"])
        self.fault = fault
        self.kinds = list(m["layer_types"])
        self.home = device or jax.devices()[0]
        put = lambda a: jax.device_put(a, self.home)
        # a layer's own leaves sit at its index among the layers of its kind
        self.index = []
        seen = {"attention": 0, "mamba": 0}
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

        def fwd(kind):
            return jax.jit(lambda lp, x: layer(_f32(lp), x, kind, m,
                                               operand))

        def bwd_update(kind):
            def fn(lp, lv, x, dx):
                _, vjp = jax.vjp(lambda p, x_: layer(p, x_, kind, m,
                                                     operand), _f32(lp), x)
                g, dx_in = vjp(dx)
                new_p, new_v = self._update(lp, lv, g)
                return dx_in, new_p, new_v, {k: _sq(a) for k, a in g.items()}
            return jax.jit(fn, donate_argnums=(0, 1))

        def head(hp, x, targets, n_tokens):
            """The loss, the gradient into x, the gradient of the norm's
            scale, and the head's share of the tied matrix's gradient."""
            def loss_fn(hp32, x_):
                return head_loss_sum(hp32, x_, targets, operand) / n_tokens
            loss, vjp = jax.vjp(loss_fn, _f32(hp), x)
            g_hp, dx = vjp(jnp.float32(1.0))
            return loss, dx, g_hp["final_ln_scale"], g_hp["embed"]

        def outer_update(outer, v_outer, g_scale, g_head, tokens, dx0):
            g = {"final_ln_scale": g_scale,
                 "embed": g_head.at[tokens].add(dx0)}
            new_p, new_v = self._update(outer, v_outer, g)
            return new_p, new_v, {k: _sq(a) for k, a in g.items()}

        self._fwd = {k: fwd(k) for k in OWN}
        self._bwd = {k: bwd_update(k) for k in OWN}
        self._head = jax.jit(head, static_argnums=(3,))
        self._outer_update = jax.jit(outer_update, donate_argnums=(0, 1))
        self._lookup = jax.jit(lambda e, t: e[t].astype(jnp.float32))

    def _update(self, p, v, g):
        """Each leaf in its own stored type."""
        mom, lr = self.mom, self.lr
        new_v = {k: (mom * v[k].astype(jnp.float32) + g[k]).astype(
            v[k].dtype) for k in p}
        new_p = {k: (p[k].astype(jnp.float32)
                     - lr * new_v[k].astype(jnp.float32)).astype(p[k].dtype)
                 for k in p}
        return new_p, new_v

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
            xs = []
            for kind, lp in zip(self.kinds, self.layers):
                xs.append(x)
                x = self._fwd[kind](lp, x)
            loss, dx, g_scale, g_head = self._head(
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
        if self.first_grad_sq is None:
            sq = {k: float(v) for k, v in outer_sq.items()}
            for kind, gsq in layer_sq:
                for k, v in gsq.items():
                    name = ("blocks." if k in COMMON else PREFIX[kind]) + k
                    sq[name] = sq.get(name, 0.0) + float(v)
            self.first_grad_sq = sq
        return float(loss)

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
