"""The Gluon ResNet-50 family: the model zoo's ``resnet50_v1`` hybridized,
cast to bfloat16 with float32 master weights, trained through
``gluon.Trainer`` + ``gluon.contrib.FusedTrainStep`` — built as
``chip_smoke.py`` leg A and ``bench.py`` build it, with the benchmark's own
weights and data from the seed — and the plain reference beside it."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ref_resnet, required_work, weights


def _closes_a_block(name):
    """True for the batch norm that ends a bottleneck's residual branch
    (the third of a block; a stage's first block has a fourth, on the
    shortcut)."""
    if "_stage" not in name or "_batchnorm" not in name:
        return False
    i = int(name.split("_batchnorm")[1].split("_")[0])
    return i == 2 or (i >= 6 and i % 3 == 0)


def make_values(shapes, seed, init):
    """The trainable parameters, in the zoo's order, from the seed, as
    float32 values that bfloat16 holds exactly.  ``shapes``: [(name,
    shape)].  Convolution weights are He-normal; the classifier's are
    normal with ``init["dense_std"]``; batch-norm scales 1 + 0.1 n, those
    that close a residual branch times ``init["last_gamma"]`` (the usual
    small-gamma start, which keeps the untrained net from amplifying
    rounding); shifts and biases small and non-zero so that no leaf starts
    degenerate."""
    def make(key):
        out = []
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if name.endswith("dense0_weight"):
                v = jax.random.normal(k, shape) * init["dense_std"]
            elif name.endswith("_weight"):
                fan_in = int(np.prod(shape[1:]))
                v = jax.random.normal(k, shape) * math.sqrt(2.0 / fan_in)
            elif name.endswith("_gamma"):
                v = 1.0 + 0.1 * jax.random.normal(k, shape)
                if _closes_a_block(name):
                    v = v * init["last_gamma"]
            else:                                   # beta, bias
                v = 0.1 * jax.random.normal(k, shape)
            out.append(ref_resnet.round_bf16(v))
        return out
    return jax.jit(make)(weights.key_from_seed(seed))


def make_batches(traffic, classes, seed, count=None):
    """([images bfloat16 [N, 3, S, S]] made on the device in one jitted
    call, labels int32 [n, N] on the host): every row different.  ``count``
    gives the first few of the traffic's batches only."""
    n, s = traffic["batch"], traffic["image_size"]
    total = traffic["n_batches"]
    count = total if count is None else min(count, total)
    key = jax.random.fold_in(weights.key_from_seed(seed), 7)

    def make(key):
        keys = jax.random.split(key, total)
        # a batch at a time, each its own buffer: no float32 draw of the
        # whole set, and no second copy when the set is dealt out
        return [jax.random.normal(keys[i], (n, 3, s, s)).astype(jnp.bfloat16)
                for i in range(count)]

    images = jax.jit(make)(key)
    labels = weights.host_rng(seed, 1).integers(
        0, classes, size=(total, n), dtype=np.int32)[:count]
    return images, labels


def host_batches(config, traffic, seed):
    """The first three batches, as the reference takes them."""
    images, labels = make_batches(traffic, config["model"]["classes"], seed,
                                  count=3)
    return [(images[i].astype(jnp.float32), labels[i]) for i in range(3)]


def _short(name):
    return name.split("_", 1)[1]


class Trainer:
    def __init__(self, config, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.contrib import FusedTrainStep
        from mxnet_tpu.gluon.model_zoo import vision

        m = config["model"]
        opt = traffic["optimizer"]
        self.items_per_step = traffic["batch"]
        ctx = mx.current_context()
        net = vision.get_model(m["zoo_name"], classes=m["classes"])
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.hybridize(static_alloc=True, static_shape=True)
        s = traffic["image_size"]
        with mx.autograd.pause():                  # finish deferred init
            net(mx.nd.zeros((1, 3, s, s), ctx=ctx))
        params = net.collect_params()
        self.train_names = [n for n, p in params.items()
                            if p.grad_req != "null"]
        self.names = [_short(n) for n in self.train_names]
        shapes = [(n, tuple(params[n].shape)) for n in self.train_names]
        values = make_values(shapes, seed, m["init"])
        for n, v in zip(self.train_names, values):
            params[n].set_data(mx.nd.array(v, ctx=ctx))
        net.cast(m["dtype"])
        self.trainer = gluon.Trainer(
            params, "sgd", {"learning_rate": opt["lr"],
                            "momentum": opt["momentum"],
                            "multi_precision": True})
        self.step_fn = FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), self.trainer)
        self.lr = float(opt["lr"])
        images, labels = make_batches(traffic, m["classes"], seed)
        # wrapped where they lie: no trip through the host
        self.batches = [
            (mx.nd.NDArray(images[i].astype(m["dtype"]), ctx=ctx),
             mx.nd.NDArray(jnp.asarray(labels[i], jnp.float32), ctx=ctx))
            for i in range(len(labels))]
        self.host_batches = [(images[i].astype(jnp.float32), labels[i])
                             for i in range(3)]
        del images
        self.net, self.params = net, params
        self.start = [v for v in values]
        self._norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)))))
        self._diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b))))

    def step(self, i):
        x, y = self.batches[i % len(self.batches)]
        return self.step_fn(x, y)

    @staticmethod
    def fetch(loss):
        return float(np.asarray(loss.asnumpy(), np.float32).mean())

    def _states(self):
        """[(momentum, master)] of the trainable parameters, as the
        optimizer holds them, in ``names`` order."""
        upd = self.trainer._updaters[0]
        index = {p.name: i for i, p in enumerate(self.trainer._params)}
        out = []
        for n in self.train_names:
            state = upd.states[index[n]]
            if not isinstance(state, tuple):       # float32: no master copy
                state = (state, self.params[n].data())
            out.append(state)
        return out

    def first_grad_norms(self):
        # after one step from a zero momentum: momentum = -lr * gradient
        return {n: float(self._norm(mom.data)) / self.lr
                for n, (mom, _w) in zip(self.names, self._states())}

    def change_norms(self):
        return {n: float(self._diff(master.data, v0))
                for n, (_m, master), v0 in zip(self.names, self._states(),
                                               self.start)}

    def fence(self):
        jax.block_until_ready([p.data().data for p in self.params.values()])

    def free(self):
        self.step_fn = self.trainer = self.net = self.params = None
        self.batches = self.start = None


def program_counters():
    from mxnet_tpu import profiler

    return {k: v for k, v in profiler.dispatch_stats().items()
            if isinstance(v, (int, float))}


def train_flops_per_item(config, traffic):
    """Required forward + backward operations an image."""
    return required_work.resnet50_train_flops_per_image(
        traffic["image_size"], config["model"]["classes"])


def train_reference_readings(config, traffic, seed, devices, host_batches,
                             operand=None, fault=None):
    m = config["model"]
    shapes = [(n, tuple(s)) for n, s in reference_shapes(m["classes"])]
    values = make_values(shapes, seed, m["init"])
    ref = ref_resnet.TrainReference(
        [_short(n) for n, _ in shapes], values, traffic["optimizer"],
        m["dtype"], operand=operand, fault=fault)
    losses = [ref.step(*host_batches[i]) for i in range(3)]
    return {"loss": losses, "grad": ref.first_grad_norms(),
            "change": ref.change_norms()}


def reference_shapes(classes):
    """[(name, shape)] of the trainable parameters in the zoo's order,
    worked out from the architecture (the reference side never asks the
    program).  Names are the zoo's, so the leaves pair up by name."""
    out = [("resnetv10_conv0_weight", (64, 3, 7, 7)),
           ("resnetv10_batchnorm0_gamma", (64,)),
           ("resnetv10_batchnorm0_beta", (64,))]
    c_in = 64
    for stage, (blocks, c_out) in enumerate(ref_resnet.STAGES, start=1):
        mid = c_out // 4
        pre = "resnetv10_stage%d_" % stage
        conv_i = bn_i = 0

        def conv(o, i, k, bias):
            nonlocal conv_i
            rows = [(pre + "conv%d_weight" % conv_i, (o, i, k, k))]
            if bias:
                rows.append((pre + "conv%d_bias" % conv_i, (o,)))
            conv_i += 1
            return rows

        def bn(c):
            nonlocal bn_i
            rows = [(pre + "batchnorm%d_gamma" % bn_i, (c,)),
                    (pre + "batchnorm%d_beta" % bn_i, (c,))]
            bn_i += 1
            return rows

        for b in range(blocks):
            out += conv(mid, c_in, 1, True) + bn(mid)
            out += conv(mid, mid, 3, False) + bn(mid)
            out += conv(c_out, mid, 1, True) + bn(c_out)
            if b == 0:
                out += conv(c_out, c_in, 1, False) + bn(c_out)
            c_in = c_out
    out += [("resnetv10_dense0_weight", (classes, c_in)),
            ("resnetv10_dense0_bias", (classes,))]
    return out
