"""Plain reference of ResNet-50 v1 as the Gluon model zoo builds it (He et
al. 2015, with the zoo's placement of the stride on a bottleneck's first 1x1
convolution and its biases on the 1x1 convolutions), trained with softmax
cross-entropy and SGD with momentum on float32 master weights.

Straightforward ``jax.numpy``/``lax`` in float32 at ``highest`` precision;
imports nothing of the program.  It is handed the parameters as an ordered
list of (name, array) in the order the architecture is walked here, which is
the order the zoo registers them: stem convolution and batch norm, then for
each bottleneck conv-bn, conv-bn, conv-bn and, on a stage's first block, the
downsample conv-bn, then the classifier.  Batch norm uses the batch's own
statistics (training mode); the running averages take no part in the loss.

As the configuration states, the forward pass sees the master weights
rounded to the working type (bfloat16); everything else is float32.
"""
import jax
import jax.numpy as jnp
from jax import lax

from .ref_transformer import OPERANDS, round_bf16

STAGES = ((3, 256), (4, 512), (6, 1024), (3, 2048))
EPS = 1e-5


class Rounding:
    """What the control rounds: ``operand`` on every product's inputs and,
    for a name that ends in ``_stored``, ``kept`` on every activation the
    program keeps in its working type (a convolution's and a batch norm's
    output) with its gradient.  The reference itself rounds nothing."""

    def __init__(self, name=None):
        same = OPERANDS[None]
        self.operand = OPERANDS[name]
        self.kept = OPERANDS[name] if (name or "").endswith("_stored") \
            else same


def conv(x, w, stride, pad, r):
    return r.kept(lax.conv_general_dilated(
        r.operand(x), r.operand(w), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))


def batch_norm(x, gamma, beta, r):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return r.kept((x - mean) * lax.rsqrt(var + EPS)
                  * gamma[None, :, None, None] + beta[None, :, None, None])


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])


class _Walk:
    """Hands out the ordered parameters as the architecture asks for
    them."""

    def __init__(self, values):
        self.values, self.at = values, 0

    def take(self):
        v = self.values[self.at]
        self.at += 1
        return v

    def conv(self, bias):
        w = self.take()
        b = self.take() if bias else None
        return w, b

    def bn(self):
        return self.take(), self.take()


def forward(values, x, operand=None):
    """``values``: the trainable parameters in order (running statistics
    left out).  x [N, 3, H, W] float32 -> logits [N, classes]."""
    r = Rounding(operand)
    p = _Walk(values)
    w, _ = p.conv(False)
    x = jax.nn.relu(batch_norm(conv(x, w, 2, 3, r), *p.bn(), r))
    x = max_pool_3x3_s2(x)
    for stage, (blocks, _c_out) in enumerate(STAGES):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            n_take = 11 + (3 if b == 0 else 0)
            block_values = values[p.at:p.at + n_take]
            p.at += n_take
            x = jax.checkpoint(
                lambda bv, x_, s=stride, d=(b == 0): bottleneck(
                    bv, x_, s, d, r))(block_values, x)
    x = jnp.mean(x, axis=(2, 3))
    w, bias = p.conv(True)
    return r.kept(jnp.einsum("nc,kc->nk", r.operand(x), r.operand(w))
                   + bias)


def bottleneck(values, x, stride, downsample, r):
    p = _Walk(values)
    w, b = p.conv(True)
    y = conv(x, w, stride, 0, r) + b[None, :, None, None]
    y = jax.nn.relu(batch_norm(y, *p.bn(), r))
    w, _ = p.conv(False)
    y = jax.nn.relu(batch_norm(conv(y, w, 1, 1, r), *p.bn(), r))
    w, b = p.conv(True)
    y = batch_norm(conv(y, w, 1, 0, r) + b[None, :, None, None], *p.bn(),
                   r)
    if downsample:
        w, _ = p.conv(False)
        x = batch_norm(conv(x, w, stride, 0, r), *p.bn(), r)
    return jax.nn.relu(y + x)


def mean_loss(values, x, labels, operand=None):
    logits = forward(values, x, operand)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


class TrainReference:
    def __init__(self, names, masters, optimizer, work_dtype, operand=None,
                 fault=None):
        self.names = list(names)
        self.master = [jnp.asarray(m, jnp.float32) for m in masters]
        self.start = list(self.master)
        self.mom = [jnp.zeros_like(m) for m in self.master]
        self.first_mom = None
        self.fault = fault
        lr, momentum = float(optimizer["lr"]), float(optimizer["momentum"])
        to_working = round_bf16 if jnp.dtype(work_dtype) == jnp.bfloat16 \
            else (lambda m: m)

        def step(master, mom, x, labels):
            working = [to_working(m) for m in master]
            loss, grads = jax.value_and_grad(mean_loss)(working, x, labels,
                                                        operand)
            new_mom = [momentum * v - lr * g for v, g in zip(mom, grads)]
            new_master = [m + v for m, v in zip(master, new_mom)]
            return loss, new_master, new_mom

        self._step = jax.jit(step, donate_argnums=(1,))
        self.lr = lr

    def step(self, x, labels):
        if self.fault == "half_batch":
            half = max(1, x.shape[0] // 2)
            x, labels = x[:half], labels[:half]
        with jax.default_matmul_precision("highest"):
            loss, self.master, self.mom = self._step(
                self.master, self.mom, jnp.asarray(x, jnp.float32),
                jnp.asarray(labels, jnp.int32))
        if self.first_mom is None:
            self.first_mom = [float(jnp.sqrt(jnp.sum(jnp.square(v))))
                              / self.lr for v in self.mom]
        return float(loss)

    def first_grad_norms(self):
        return dict(zip(self.names, self.first_mom))

    def change_norms(self):
        return {n: float(jnp.sqrt(jnp.sum(jnp.square(a - b))))
                for n, a, b in zip(self.names, self.master, self.start)}
