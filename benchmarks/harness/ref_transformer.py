"""Plain reference of the LM the benchmark's Pythia-sized configurations run.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching tricks.  It imports nothing of the program and
is handed the weights the benchmark made.  The block is this repo's
``TransformerLM`` block as its configuration file states it (RMSNorm with
eps 1e-6, fused QKV, causal multi-head attention with no positional term,
tanh-GELU MLP, sequential residuals, untied output head), not GPT-NeoX's.

Training follows the configuration's optimizer: SGD with momentum on
parameters and momentum *stored* in the configuration's parameter type
(``v <- m v + g``, ``p <- p - lr v``, each rounded once to storage).  The
mathematics is float32; only what the configuration says is kept in bfloat16
is rounded, because a parameter that cannot hold a small update does not
move in any implementation.

To fit beside nothing else on a chip the state is kept a layer at a time and
each layer is updated as soon as its gradient is known; layers may be spread
over several devices.  ``operand`` swaps in the control's operand rounding
(float8), and ``fault`` plants a training fault, so the same code gives the
readings the limits are set from.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_LEAVES = ("ln1_scale", "ln2_scale", "wqkv", "wo", "w_up", "w_down")


def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


# Roundings are ``lax.reduce_precision``, never a pair of casts: XLA may
# drop ``x.astype(low).astype(float32)`` as excess precision, and did on the
# chip (PERF.md, Findings, PR 25).
def round_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def round_e4m3(x):
    """Float8 with 4 exponent and 3 mantissa bits and one scale a tensor
    (amax -> 240, the largest finite value of the IEEE-style format)."""
    scale = jnp.max(jnp.abs(x)) / 240.0 + 1e-30
    return jax.lax.reduce_precision(
        x / scale, exponent_bits=4, mantissa_bits=3) * scale


def _straight_through(rounding):
    """An operand rounded on the way in; the gradient passes as if nothing
    was rounded."""
    return lambda x: x + jax.lax.stop_gradient(rounding(x) - x)


def _stored(rounding):
    """A tensor *kept* in the lower precision: rounded on the way forward
    and its gradient rounded, by the same rule, on the way back."""
    @jax.custom_vjp
    def keep(x):
        return rounding(x)
    keep.defvjp(lambda x: (rounding(x), None), lambda _, g: (rounding(g),))
    return keep


fp8_operand = _straight_through(round_e4m3)
bf16_operand = _straight_through(round_bf16)
fp8_stored = _stored(round_e4m3)

# by the name a configuration's ``precision.control`` gives
OPERANDS = {None: lambda x: x, "float32": lambda x: x,
            "float8_e4m3": fp8_operand, "bfloat16": bf16_operand,
            "float8_e4m3_stored": fp8_stored}


def block(lp, x, n_heads, operand=None, keep_rows=1):
    """One block on x [B, T, E].  ``keep_rows`` > 1 plants the missing
    exchange of a row-parallel product: only the first 1/keep_rows of the
    contraction is summed."""
    q_ = OPERANDS[operand]
    b, t, e = x.shape
    d = e // n_heads

    def mm(a, w, row_parallel=False):
        if row_parallel and keep_rows > 1:
            k = w.shape[0] // keep_rows
            a, w = a[..., :k], w[:k]
        return jnp.einsum("bte,ef->btf", q_(a), q_(w))

    h = rmsnorm(x, lp["ln1_scale"])
    qkv = mm(h, lp["wqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def attend(qkv_row):
        qr, kr, vr = (a.reshape(t, n_heads, d) for a in qkv_row)
        s = jnp.einsum("qhd,khd->hqk", q_(qr), q_(kr)) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q_(p), q_(vr)).reshape(t, e)

    attn = jax.lax.map(attend, (q, k, v))
    x = x + mm(attn, lp["wo"], row_parallel=True)
    h = rmsnorm(x, lp["ln2_scale"])
    up = gelu_tanh(mm(h, lp["w_up"]))
    return x + mm(up, lp["w_down"], row_parallel=True)


def head_logits(hp, x, operand=None):
    """x [n, E] -> logits [n, V]."""
    q_ = OPERANDS[operand]
    h = rmsnorm(x, hp["final_ln_scale"])
    return jnp.einsum("ne,ev->nv", q_(h), q_(hp["unembed"]))


def head_loss_sum(hp, x, targets, operand=None):
    """Sum over all tokens of the cross-entropy; x [B, T, E]."""
    @jax.checkpoint
    def row(args):
        xr, tr = args
        logits = head_logits(hp, xr, operand)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)
    return jnp.sum(jax.lax.map(row, (x, targets)))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _sq(a):
    return jnp.sum(jnp.square(a.astype(jnp.float32)))


class TrainReference:
    """The reference trainer.  ``params`` is the flat dict the benchmark
    made; it is split into layers (and copied) here."""

    def __init__(self, model, params, optimizer, devices=None, operand=None,
                 fault=None, tp=1):
        self.m = model
        self.lr = float(optimizer["lr"])
        self.mom = float(optimizer["momentum"])
        self.operand = operand
        self.fault = fault
        self.keep_rows = tp if fault == "no_exchange" else 1
        devices = list(devices or jax.devices()[:1])
        self.home = devices[0]
        layers = model["n_layers"]
        self.dev_of = [devices[i % len(devices)] for i in range(layers)]
        self.store = jnp.dtype(model["dtype"])
        put = jax.device_put
        self.layers = []
        for i in range(layers):
            lp = {k: put(params["blocks." + k][i], self.dev_of[i])
                  for k in BLOCK_LEAVES}
            self.layers.append(lp)
        # a copy: the updates donate these, and the caller keeps its own
        self.outer = {k: put(jnp.copy(params[k]), self.home)
                      for k in ("embed", "final_ln_scale", "unembed")}
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        self.v_layers = [zeros(lp) for lp in self.layers]
        self.v_outer = zeros(self.outer)
        self.first_grad_sq = None
        n_heads = model["n_heads"]
        operand_, keep = self.operand, self.keep_rows

        def fwd(lp, x):
            return block(_f32(lp), x, n_heads, operand_, keep)

        def bwd_update(lp, lv, x, dx):
            _, vjp = jax.vjp(lambda p, x_: block(p, x_, n_heads, operand_,
                                                 keep), _f32(lp), x)
            g, dx_in = vjp(dx)
            new_p, new_v = self._update(lp, lv, g)
            return dx_in, new_p, new_v, {k: _sq(a) for k, a in g.items()}

        def head(hp, hv, x, targets, n_tokens):
            def loss_fn(hp32, x_):
                return head_loss_sum(hp32, x_, targets, operand_) / n_tokens
            loss, vjp = jax.vjp(loss_fn, _f32(hp), x)
            g_hp, dx = vjp(jnp.float32(1.0))
            new_hp, new_hv = self._update(hp, hv, g_hp)
            return loss, dx, new_hp, new_hv, {k: _sq(a)
                                              for k, a in g_hp.items()}

        def embed_update(embed, ev, tokens, dx0):
            g = jnp.zeros(embed.shape, jnp.float32).at[tokens].add(dx0)
            new_p, new_v = self._update({"embed": embed}, {"embed": ev},
                                        {"embed": g})
            return new_p["embed"], new_v["embed"], _sq(g)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd_update, donate_argnums=(0, 1))
        self._head = jax.jit(head, static_argnums=(4,))
        self._embed_update = jax.jit(embed_update, donate_argnums=(0, 1))
        self._lookup = jax.jit(lambda e, t: e[t].astype(jnp.float32))

    def _update(self, p, v, g):
        mom, lr, store = self.mom, self.lr, self.store
        new_v = {k: (mom * v[k].astype(jnp.float32) + g[k]).astype(store)
                 for k in p}
        new_p = {k: (p[k].astype(jnp.float32)
                     - lr * new_v[k].astype(jnp.float32)).astype(store)
                 for k in p}
        return new_p, new_v

    def step(self, tokens):
        """One step on tokens [B, T + 1]; returns the loss as a float."""
        tokens = np.asarray(tokens)
        if self.fault == "half_batch":
            tokens = tokens[: max(1, tokens.shape[0] // 2)]
        x_ids = jnp.asarray(tokens[:, :-1])
        y_ids = jnp.asarray(tokens[:, 1:])
        with jax.default_matmul_precision("highest"):
            x = self._lookup(self.outer["embed"], x_ids)
            xs = []
            for i, lp in enumerate(self.layers):
                x = jax.device_put(x, self.dev_of[i])
                xs.append(x)
                x = self._fwd(lp, x)
            x = jax.device_put(x, self.home)
            hp = {k: self.outer[k] for k in ("final_ln_scale", "unembed")}
            hv = {k: self.v_outer[k] for k in ("final_ln_scale", "unembed")}
            loss, dx, new_hp, new_hv, gsq = self._head(
                hp, hv, x, y_ids, int(y_ids.size))
            self.outer.update(new_hp)
            self.v_outer.update(new_hv)
            grad_sq = {k: v for k, v in gsq.items()}
            block_sq = {k: [] for k in BLOCK_LEAVES}
            for i in reversed(range(len(self.layers))):
                dx = jax.device_put(dx, self.dev_of[i])
                dx, self.layers[i], self.v_layers[i], gsq = self._bwd(
                    self.layers[i], self.v_layers[i], xs[i], dx)
                xs[i] = None
                for k in BLOCK_LEAVES:
                    block_sq[k].append(gsq[k])
            dx = jax.device_put(dx, self.home)
            (self.outer["embed"], self.v_outer["embed"],
             grad_sq["embed"]) = self._embed_update(
                 self.outer["embed"], self.v_outer["embed"], x_ids, dx)
        if self.first_grad_sq is None:
            sq = {k: float(v) for k, v in grad_sq.items()}
            for k in BLOCK_LEAVES:
                sq["blocks." + k] = float(sum(float(s) for s in block_sq[k]))
            self.first_grad_sq = sq
        return float(loss)

    def first_grad_norms(self):
        return {k: math.sqrt(v) for k, v in self.first_grad_sq.items()}

    def change_norms(self, init_leaf):
        """Per-leaf norm of (parameters now - parameters at the start);
        ``init_leaf(name)`` gives a leaf's starting value."""
        diff_sq = jax.jit(lambda a, b: _sq(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))
        out = {}
        for k in ("embed", "final_ln_scale", "unembed"):
            p0 = jax.device_put(init_leaf(k), self.home)
            out[k] = math.sqrt(float(diff_sq(self.outer[k], p0)))
        for k in BLOCK_LEAVES:
            p0 = init_leaf("blocks." + k)
            total = 0.0
            for i, lp in enumerate(self.layers):
                total += float(diff_sq(
                    lp[k], jax.device_put(p0[i], self.dev_of[i])))
            out["blocks." + k] = math.sqrt(total)
        return out


# -- serving ----------------------------------------------------------------
def make_forward(model, operand=None):
    """tokens [1, T] -> logits [T, V], float32, no cache."""
    n_heads = model["n_heads"]
    names = ["blocks." + k for k in BLOCK_LEAVES]

    @jax.jit
    def forward(params, tokens):
        x = params["embed"][tokens].astype(jnp.float32)

        def body(x, lp):
            return block(_f32(dict(zip(BLOCK_LEAVES, lp))), x, n_heads,
                         operand), None

        x, _ = jax.lax.scan(body, x, tuple(params[n] for n in names))
        hp = _f32({k: params[k] for k in ("final_ln_scale", "unembed")})
        return head_logits(hp, x[0], operand)

    return forward


def served_token_gaps(forward, params, prompt, served, pad_to=128,
                      control_forward=None):
    """The gap by which each served token's reference logit lies below the
    reference's best at its position, as an array over the served tokens.
    With ``control_forward``, instead the gap of the token the control puts
    first at each of those positions."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    total = prompt.size + served.size
    t_pad = -(-total // pad_to) * pad_to
    tokens = np.zeros((1, t_pad), np.int32)
    tokens[0, :prompt.size] = prompt
    tokens[0, prompt.size:total] = served
    with jax.default_matmul_precision("highest"):
        logits = forward(params, jnp.asarray(tokens))
        # the logits at position i predict token i + 1
        rows = logits[prompt.size - 1: total - 1]
        if control_forward is not None:
            picked = jnp.argmax(control_forward(params, jnp.asarray(tokens))
                                [prompt.size - 1: total - 1], axis=-1)
        else:
            picked = jnp.asarray(served)
        best = jnp.max(rows, axis=-1)
        got = jnp.take_along_axis(rows, picked[:, None], axis=-1)[:, 0]
    return np.asarray(best - got, np.float64)
