"""The hybrid state-space LM family (Mamba-1 mixers beside attention, as
AI21's Jamba): how the benchmark drives the program's ``TransformerLM`` with
``layer_types`` through ``make_train_step``, and builds the plain reference
beside it.  Program imports stay inside the functions that drive the
program; the reference side imports none.  Training only: the program has no
paged decode for such a model yet."""

import jax
import jax.numpy as jnp

from . import ref_hybrid_ssm_lm as ref
from . import required_work_hybrid_ssm_lm as work
from . import weights_hybrid_ssm_lm as hybrid_weights
from .family_transformer_lm import _diff_norm, _leaf_norms, host_batches


class Trainer:
    """``jax.jit(make_train_step(model))`` with donated state, fed from a
    rotating set of device-resident token batches."""

    def __init__(self, config, traffic, seed, devices):
        from mxnet_tpu.models import TransformerConfig, TransformerLM
        from mxnet_tpu.models.transformer import make_train_step

        self.m = m = config["model"]
        self.seed = seed
        self.batch, self.seq = traffic["batch"], traffic["seq"]
        self.items_per_step = self.batch * self.seq
        opt = traffic["optimizer"]
        model = TransformerLM(TransformerConfig(**m))
        self.params = hybrid_weights.init(m, seed)
        self.velocity = jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))(self.params)
        self.step_fn = jax.jit(
            make_train_step(model, lr=opt["lr"], momentum=opt["momentum"]),
            donate_argnums=(0, 1))
        self.host_batches = host_batches(config, traffic, seed)
        self.batches = [(jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:]))
                        for b in self.host_batches]
        self._norms = jax.jit(_leaf_norms)
        self._diff = jax.jit(_diff_norm)

    def step(self, i):
        """Enqueue step ``i``; returns the loss still on the device."""
        x, y = self.batches[i % len(self.batches)]
        self.params, self.velocity, loss = self.step_fn(
            self.params, self.velocity, x, y)
        return loss

    @staticmethod
    def fetch(loss):
        return float(loss)

    def first_grad_norms(self):
        """After one step from a zero momentum the momentum is the gradient
        the optimizer was given."""
        return {k: float(v) for k, v in self._norms(self.velocity).items()}

    def change_norms(self):
        return {name: float(self._diff(
            self.params[name],
            hybrid_weights.init_leaf(self.m, self.seed, name)))
            for name in self.params}

    def fence(self):
        jax.block_until_ready((self.params, self.velocity))

    def free(self):
        self.params = self.velocity = self.batches = None


def program_counters():
    """The program's own counts, read as deltas over the window."""
    from mxnet_tpu import profiler, telemetry

    out = {k: v for k, v in profiler.dispatch_stats().items()
           if isinstance(v, (int, float))}
    for k, v in telemetry.registry().snapshot()["counters"].items():
        if k.startswith(("pallas.select.", "pallas.ssm_scan.")):
            out[k] = v
    return out


def train_reference_readings(config, traffic, seed, devices, host_batches,
                             operand=None, fault=None):
    """The reference's three steps on the program's first three batches."""
    m = config["model"]
    params = hybrid_weights.init(m, seed)
    trainer = ref.TrainReference(m, params, traffic["optimizer"],
                                 device=devices[0], operand=operand,
                                 fault=fault)
    del params
    losses = [trainer.step(host_batches[i]) for i in range(3)]
    return {"loss": losses, "grad": trainer.first_grad_norms(),
            "change": trainer.change_norms(
                lambda name: hybrid_weights.init_leaf(m, seed, name))}


# -- the work the shapes require (read by layer_metrics/) ---------------------
def train_flops_per_item(config, traffic):
    """Required forward + backward operations a token."""
    b, t = traffic["batch"], traffic["seq"]
    return work.train_flops_per_step(config["model"], b, t) / (b * t)


def kernels_required_per_step(config, traffic, peaks):
    return work.pallas_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def scan_required_per_step(config, traffic, peaks):
    return work.scan_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def scan_call_seconds(config, custom_calls):
    """Seconds a reduced trace's ``custom_calls`` ([(identity, seconds)])
    hold of the selective scan.  The reduction names a custom call by its
    shapes: the scan's forward and backward kernels, and no other kernel of
    the step, take the decay matrix ``A`` laid out states-first, a float32
    ``[N, Di]`` operand."""
    s = hybrid_weights.sizes(config["model"])
    needle = "f32[%d,%d]" % (s["n"], s["di"])
    return sum(seconds for identity, seconds in custom_calls
               if needle in identity.split("<-", 1)[-1])
