"""The convolution-and-attention, routed-experts LM family (LFM2): how the
benchmark drives the program's ``TransformerLM`` with gated short
convolutions beside GQA attention with q/k norms (``layer_types``), dense
and expert layers (``mlp_types``), a sigmoid router whose selection bias the
step moves from its own load (``moe_router``, ``moe_expert_bias``) and a
tied head through ``make_train_step``, and builds the plain reference beside
it.  Program imports stay inside the functions that drive the program; the
reference side imports none.  Training only: the program has no paged decode
for such a model yet.

The hooks the harness and the per-layer readers find here by name: the
``Trainer`` (``step``, ``fetch``, ``first_grad_norms``, ``change_norms``,
``fence``, ``free``), ``host_batches``, ``program_counters``,
``train_reference_readings`` (``fault``: ``half_batch``,
``softmax_router``, ``no_expert_bias``, ``no_qk_norm``, ``conv_shifted``),
``train_flops_per_item`` (``train_mfu``), ``kernels_required_per_step``
(``pallas_train_roofline``), ``gmm_call_seconds`` /
``gmm_required_per_step`` (the two ``moe_gmm_*`` readers),
``flash_call_seconds`` / ``flash_required_per_step``
(``flash_time_share.train``, ``flash_roofline``),
``short_conv_call_seconds`` / ``short_conv_required_per_step``
(``short_conv_time_share.train``, ``short_conv_roofline``)."""
import re
import weakref

import jax
import jax.numpy as jnp

from . import ref_conv_moe_lm as ref
from . import required_work_conv_moe_lm as work
from . import runtime
from . import weights_conv_moe_lm as conv_weights
from .family_swa_moe_lm import _kind
from .family_transformer_lm import _diff_norm, _leaf_norms, host_batches

_LIVE = []          # the trainer at work, for ``program_counters``


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
        self.params = conv_weights.init(m, seed)
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
        self._share = jax.jit(model.held_slot_share)
        self.steps = 0
        self.held_slot_share()              # compiled in set-up
        _LIVE[:] = [weakref.ref(self)]

    def step(self, i):
        """Enqueue step ``i``; returns the loss still on the device."""
        x, y = self.batches[i % len(self.batches)]
        self.params, self.velocity, loss = self.step_fn(
            self.params, self.velocity, x, y)
        self.steps += 1
        return loss

    @staticmethod
    def fetch(loss):
        return float(loss)

    def held_slot_share(self):
        """Share of the (token, slot) pairs of the first batch that the
        router, with the parameters (and the bias) as they are, sends to
        held experts."""
        return float(self._share(self.params, self.batches[0][0]))

    def first_grad_norms(self):
        """After one step from a zero momentum the momentum is the gradient
        the optimizer was given (the expert bias's stays zero)."""
        return {k: float(v) for k, v in self._norms(self.velocity).items()}

    def change_norms(self):
        return {name: float(self._diff(
            self.params[name],
            conv_weights.init_leaf(self.m, self.seed, name)))
            for name in self.params}

    def fence(self):
        jax.block_until_ready((self.params, self.velocity))

    def free(self):
        self.params = self.velocity = self.batches = None
        _LIVE[:] = []


def program_counters():
    """The program's own counts, read as deltas over the window.  The kind's
    driver reads them at the window's two ends, outside it: each reading
    also says where the routing stands (the held experts' share of the
    slots moves as the router and the bias move)."""
    from mxnet_tpu import profiler, telemetry

    trainer = _LIVE[0]() if _LIVE else None
    if trainer is not None and trainer.params is not None:
        runtime.say("held-slot share after %d steps: %.4f"
                    % (trainer.steps, trainer.held_slot_share()))
    out = {k: v for k, v in profiler.dispatch_stats().items()
           if isinstance(v, (int, float))}
    for k, v in telemetry.registry().snapshot()["counters"].items():
        if k.startswith(("pallas.select.", "pallas.flash.", "pallas.gmm.",
                         "moe.", "lm.")):
            out[k] = v
    return out


def train_reference_readings(config, traffic, seed, devices, host_batches,
                             operand=None, fault=None):
    """The reference's three steps on the program's first three batches."""
    m = config["model"]
    params = conv_weights.init(m, seed)
    trainer = ref.TrainReference(m, params, traffic["optimizer"],
                                 device=devices[0], operand=operand,
                                 fault=fault)
    del params
    losses = [trainer.step(host_batches[i]) for i in range(3)]
    runtime.say("held-slot share of the %s's three steps: %s (required "
                "work counts %.4f)"
                % ("reference" if operand is None and fault is None
                   else operand or fault,
                   " ".join("%.4f" % v for v in trainer.held_shares),
                   work.expected_experts_per_token(m) / m["moe_top_k"]))
    return {"loss": losses, "grad": trainer.first_grad_norms(),
            "change": trainer.change_norms(
                lambda name: conv_weights.init_leaf(m, seed, name))}


# -- the work the shapes require (read by layer_metrics/) ---------------------
def train_flops_per_item(config, traffic):
    """Required forward + backward operations a token: the products, the
    causal scores, the routed experts at their expectation."""
    b, t = traffic["batch"], traffic["seq"]
    return work.train_flops_per_step(config["model"], b, t) / (b * t)


def kernels_required_per_step(config, traffic, peaks):
    return work.pallas_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def gmm_required_per_step(config, traffic, peaks):
    return work.gmm_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def flash_required_per_step(config, traffic, peaks):
    return work.flash_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def short_conv_required_per_step(config, traffic, peaks):
    return work.short_conv_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def gmm_call_seconds(config, custom_calls):
    """Seconds a reduced trace's ``custom_calls`` ([(identity, seconds)])
    hold of the grouped product: its kernels, and no other kernel of the
    step, take (forward, ``dx``) or give (``dW``) the held experts' stacked
    matrix, ``[held, E, F]`` or ``[held, F, E]``."""
    s = conv_weights.sizes(config["model"])
    needles = ("%s[%d,%d,%d]" % (_kind(config), s["held"], s["e"], s["fe"]),
               "%s[%d,%d,%d]" % (_kind(config), s["held"], s["fe"], s["e"]))
    return sum(seconds for identity, seconds in custom_calls
               if any(n in identity for n in needles))


def flash_call_seconds(config, traffic, custom_calls):
    """Seconds a reduced trace's ``custom_calls`` hold of the three flash
    kernels (forward, dQ, dK/dV): they, and no other kernel of the step,
    take ``q`` heads first, ``[B, H, T, D]`` in the model's type."""
    s = conv_weights.sizes(config["model"])
    needle = "%s[%d,%d,%d,%d]" % (_kind(config), traffic["batch"],
                                  s["heads"], traffic["seq"], s["d"])
    return sum(seconds for identity, seconds in custom_calls
               if needle in identity.split("<-", 1)[-1])


def short_conv_call_seconds(config, custom_calls):
    """Seconds a reduced trace's ``custom_calls`` hold of the gated
    convolution's two kernels: they, and no other kernel of the step, take
    the taps ``[K, E]`` in the model's type as an operand."""
    s = conv_weights.sizes(config["model"])
    taps = re.compile(r"(^|,)%s\[%d,%d\](x\d+)?(,|$)" % (
        _kind(config), s["K"], s["e"]))
    return sum(seconds for identity, seconds in custom_calls
               if taps.search(identity.split("<-", 1)[-1]))
