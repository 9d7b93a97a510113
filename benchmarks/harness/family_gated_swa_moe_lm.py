"""The gated window-and-full-attention, routed-experts LM family (Laguna):
how the benchmark drives the program's ``TransformerLM`` with query heads set
by layer (``attn_heads``), a per-head output gate (``attn_gate``), a rotary
term of each layer's own with a partial YaRN part (``attn_rope`` settings),
a window (``attn_windows``), a dense leading layer beside expert layers with
a shared expert and a routed scale (``mlp_types``, ``moe_routed_scale``)
through ``make_train_step``, and builds the plain reference beside it.
Program imports stay inside the functions that drive the program; the
reference side imports none.  Training only: the program has no paged decode
for such a model yet.

The hooks the harness and the per-layer readers find here by name: the
``Trainer`` (``step``, ``fetch``, ``first_grad_norms``, ``change_norms``,
``fence``, ``free``), ``host_batches``, ``program_counters``,
``train_reference_readings`` (``fault``: ``half_batch``, ``no_gate``,
``whole_rotary``, ``unscaled_routing``), ``train_flops_per_item``
(``train_mfu``), ``kernels_required_per_step`` (``pallas_train_roofline``),
``gmm_call_seconds`` / ``gmm_required_per_step`` (the two ``moe_gmm_*``
readers), ``flash_call_seconds`` / ``flash_required_per_step``
(``flash_time_share.train``, ``flash_roofline``),
``flash_band_call_seconds`` / ``flash_band_required_per_step``
(``flash_band_time_share.train``, ``flash_band_roofline``)."""
import math
import weakref

import jax
import jax.numpy as jnp

from . import ref_gated_swa_moe_lm as ref
from . import required_work_gated_swa_moe_lm as work
from . import runtime
from . import weights_gated_swa_moe_lm as gated_weights
from .family_conv_moe_lm import program_counters as _counters
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
        self.params = gated_weights.init(m, seed)
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
        # the router probe is first traced by ``program_counters``, after
        # the step (still in set-up): traced before it, the probe leaves the
        # jitted kernel entry points' bodies (`moe_slot_sum`) in JAX's
        # caches, and whether the step's text carries those bodies' source
        # locations then depends on what the collector has freed, so the
        # step's persistent-cache key changes from run to run
        self._share = jax.jit(model.held_slot_share)
        self.steps = 0
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
        router, with the parameters as they are, sends to held experts."""
        return float(self._share(self.params, self.batches[0][0]))

    def first_grad_norms(self):
        """After one step from a zero momentum the momentum is the gradient
        the optimizer was given."""
        return {k: float(v) for k, v in self._norms(self.velocity).items()}

    def change_norms(self):
        return {name: float(self._diff(
            self.params[name],
            gated_weights.init_leaf(self.m, self.seed, name)))
            for name in self.params}

    def fence(self):
        jax.block_until_ready((self.params, self.velocity))

    def free(self):
        self.params = self.velocity = self.batches = None
        _LIVE[:] = []


def program_counters():
    """The program's own counts, read as deltas over the window, and where
    the routing stands at each end (`family_conv_moe_lm.program_counters`,
    handed this family's trainer)."""
    trainer = _LIVE[0]() if _LIVE else None
    if trainer is not None and trainer.params is not None:
        runtime.say("held-slot share after %d steps: %.4f"
                    % (trainer.steps, trainer.held_slot_share()))
    return _counters()


def train_reference_readings(config, traffic, seed, devices, host_batches,
                             operand=None, fault=None):
    """The reference's three steps on the program's first three batches.

    Where the persistent compile cache is capped in size
    (``JAX_COMPILATION_CACHE_MAX_SIZE``), the reference's programs are not
    written to it: the step, the router's probe and the reference together
    pass a cap of 192 MiB, and the cache's LRU eviction then drops the
    step, which the next run's set-up then compiles anew (on a v5e ≈ 60 s
    of a set-up of 100-137 s)."""
    key = "jax_persistent_cache_min_compile_time_secs"
    min_s = getattr(jax.config, key)
    if jax.config.jax_compilation_cache_max_size > 0:
        jax.config.update(key, math.inf)
    try:
        return _reference_readings(config, traffic, seed, devices,
                                   host_batches, operand, fault)
    finally:
        jax.config.update(key, min_s)


def _reference_readings(config, traffic, seed, devices, host_batches,
                        operand, fault):
    m = config["model"]
    params = gated_weights.init(m, seed)
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
                lambda name: gated_weights.init_leaf(m, seed, name))}


# -- the work the shapes require (read by layer_metrics/) ---------------------
def train_flops_per_item(config, traffic):
    """Required forward + backward operations a token: attention over the
    pairs each layer's mask keeps, the routed experts at their expectation."""
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


def flash_band_required_per_step(config, traffic, peaks):
    """The flash kernels of the window layers alone, over the band's
    pairs."""
    m = config["model"]
    return work.flash_required_per_step(
        m, traffic["batch"], traffic["seq"], peaks, work.window_layers(m))


def gmm_call_seconds(config, custom_calls):
    """Seconds a reduced trace's ``custom_calls`` ([(identity, seconds)])
    hold of the grouped product: its kernels, and no other kernel of the
    step, take (forward, ``dx``) or give (``dW``) the held experts' stacked
    matrix, ``[held, E, F]`` or ``[held, F, E]``."""
    s = gated_weights.sizes(config["model"])
    needles = ("%s[%d,%d,%d]" % (_kind(config), s["held"], s["e"], s["fe"]),
               "%s[%d,%d,%d]" % (_kind(config), s["held"], s["fe"], s["e"]))
    return sum(seconds for identity, seconds in custom_calls
               if any(n in identity for n in needles))


def _flash_seconds(config, traffic, custom_calls, heads):
    """Seconds of the flash kernels' calls whose operands hold ``q`` heads
    first, ``[B, H, T, D]`` in the model's type, at one of ``heads``: they,
    and no other kernel of the step, take such an operand (the key/value
    heads broadcast to their query heads have its shape too)."""
    s = gated_weights.sizes(config["model"])
    needles = ["%s[%d,%d,%d,%d]" % (_kind(config), traffic["batch"], h,
                                    traffic["seq"], s["d"])
               for h in sorted(set(heads))]
    return sum(seconds for identity, seconds in custom_calls
               if any(n in identity.split("<-", 1)[-1] for n in needles))


def flash_call_seconds(config, traffic, custom_calls):
    """Seconds a reduced trace's ``custom_calls`` hold of the three flash
    kernels (forward, dQ, dK/dV) of every layer, full and window, picked by
    their ``q`` at each head count the layers have."""
    return _flash_seconds(config, traffic, custom_calls,
                          gated_weights.sizes(config["model"])["heads"])


def flash_band_call_seconds(config, traffic, custom_calls):
    """Seconds of the window layers' flash calls alone, picked by the ``q``
    only they take: their head count, which no full layer has."""
    s = gated_weights.sizes(config["model"])
    band = {s["heads"][i] for i in work.window_layers(config["model"])}
    full = {h for h, w in zip(s["heads"], s["windows"]) if not w}
    assert not band & full, \
        "a window layer's head count is a full layer's too: its calls " \
        "cannot be told apart by their shapes"
    return _flash_seconds(config, traffic, custom_calls, band)
