"""The transformer-LM family: how the benchmark drives the program's
``TransformerLM`` (training step, generation server) and builds the plain
reference beside it.  Program imports stay inside the functions that drive
the program; the reference side imports none."""

import jax
import jax.numpy as jnp
import numpy as np

from . import ref_transformer, required_work, weights


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


class Trainer:
    """``jax.jit(make_train_step(model[, rules]))`` with donated state, fed
    from a rotating set of device-resident token batches."""

    def __init__(self, config, traffic, seed, devices):
        from mxnet_tpu.models import TransformerConfig, TransformerLM
        from mxnet_tpu.models.transformer import (default_rules,
                                                  make_train_step)

        self.m = m = config["model"]
        self.seed = seed
        self.batch, self.seq = traffic["batch"], traffic["seq"]
        self.items_per_step = self.batch * self.seq
        opt = traffic["optimizer"]
        model = TransformerLM(TransformerConfig(**m))
        self.mesh, self.shardings, rules, data_sh = None, None, None, None
        if traffic.get("mesh"):
            from jax.sharding import NamedSharding, PartitionSpec
            from mxnet_tpu.parallel import make_mesh
            from mxnet_tpu.parallel.sharding import param_sharding

            self.mesh = make_mesh(devices=devices, **traffic["mesh"])
            self.mesh.__enter__()
            rules = default_rules()
            shapes = weights.lm_leaf_shapes(m)
            self.shardings = {
                n: param_sharding(rules.spec_for(n), self.mesh,
                                  shape=shapes[n][0]) for n in shapes}
            data_sh = NamedSharding(self.mesh.mesh, PartitionSpec())
        self.params = weights.lm_init(m, seed, self.shardings)
        self.velocity = jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
            out_shardings=self.shardings)(self.params)
        self.step_fn = jax.jit(
            make_train_step(model, lr=opt["lr"], momentum=opt["momentum"],
                            rules=rules), donate_argnums=(0, 1))
        self.host_batches = weights.token_batches(
            seed, traffic["n_batches"], self.batch, self.seq,
            m["vocab_size"])
        put = (lambda a: jax.device_put(a, data_sh)) if data_sh is not None \
            else jnp.asarray
        self.batches = [(put(b[:, :-1]), put(b[:, 1:]))
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
        out = {}
        for name in self.params:
            sh = None if self.shardings is None else self.shardings[name]
            p0 = weights.lm_init_leaf(self.m, self.seed, name, sh)
            out[name] = float(self._diff(self.params[name], p0))
        return out

    def fence(self):
        jax.block_until_ready((self.params, self.velocity))

    def free(self):
        self.params = self.velocity = self.batches = None
        if self.mesh is not None:
            self.mesh.__exit__(None, None, None)
            self.mesh = None


def host_batches(config, traffic, seed):
    return weights.token_batches(seed, traffic["n_batches"],
                                 traffic["batch"], traffic["seq"],
                                 config["model"]["vocab_size"])


def program_counters():
    """The program's own counts, read as deltas over the window."""
    from mxnet_tpu import profiler, telemetry

    out = {k: v for k, v in profiler.dispatch_stats().items()
           if isinstance(v, (int, float))}
    for k, v in telemetry.registry().snapshot()["counters"].items():
        if k.startswith("pallas.select."):
            out[k] = v
    return out


def train_reference_readings(config, traffic, seed, devices, host_batches,
                             operand=None, fault=None):
    """The reference's three steps on the program's first three batches."""
    m = config["model"]
    shardings = None
    if len(devices) > 1:
        # the benchmark's own layout, only so that the weights fit while
        # they are dealt out to the layers' devices
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.asarray(devices), ("x",))
        shardings = {n: NamedSharding(mesh, PartitionSpec(
            "x" if shape[0] % len(devices) == 0 else None))
            for n, (shape, _f) in weights.lm_leaf_shapes(m).items()}
    params = weights.lm_init(m, seed, shardings)
    tp = int((traffic.get("mesh") or {}).get("tp", 1))
    ref = ref_transformer.TrainReference(
        m, params, traffic["optimizer"], devices=devices, operand=operand,
        fault=fault, tp=tp)
    del params
    losses = [ref.step(host_batches[i]) for i in range(3)]

    def init_leaf(name):
        sh = None if shardings is None else shardings[name]
        return weights.lm_init_leaf(m, seed, name, sh)

    return {"loss": losses, "grad": ref.first_grad_norms(),
            "change": ref.change_norms(init_leaf)}


# -- the work the shapes require (read by layer_metrics/) ---------------------
def train_flops_per_item(config, traffic):
    """Required forward + backward operations a token."""
    b, t = traffic["batch"], traffic["seq"]
    return required_work.lm_train_flops_per_step(config["model"], b, t) / (
        b * t)


def kernels_required_per_step(config, traffic, peaks):
    return required_work.pallas_required_per_step(
        config["model"], traffic["batch"], traffic["seq"], peaks)


def serve_flops(config, tokens):
    return required_work.lm_serve_flops(config["model"], tokens)


def decode_required_bytes(config, live_lens):
    return required_work.decode_required_bytes(config["model"], live_lens)


# -- serving ----------------------------------------------------------------
def make_server(config, traffic, seed, devices):
    """``GenerationServer`` over the model with the benchmark's weights.
    Returns (server, params)."""
    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM

    m = config["model"]
    s = traffic["server"]
    model = TransformerLM(TransformerConfig(**m))
    params = weights.lm_init(m, seed)
    gcfg = GenerationConfig(
        page_size=s["page_size"], max_pages=s["max_pages"],
        max_slots=s["max_slots"], max_new_tokens=s["max_new_tokens"],
        max_seq_len=s["max_seq_len"], slot_buckets=s["slot_buckets"],
        prefill_buckets=s["prefill_buckets"], temperature=0.0)
    srv = GenerationServer(model, params, gcfg, max_queue=s["max_queue"],
                           deadline_ms=s["deadline_ms"])
    # engine.decode ends in ``logits[:n]`` on the device, one tiny program
    # for each number of active slots; the ramp only ever sees a full
    # bucket, so make the others now and none compiles inside the window
    for bucket in srv.engine.slot_chain:
        probe = jnp.zeros((bucket, m["vocab_size"]), jnp.float32)
        for n in range(1, bucket + 1):
            np.asarray(probe[:n])
    return srv, params


def serve_reference_gaps(config, seed, sample, control=False):
    """For each (prompt, served tokens) of ``sample``: the gaps of the served
    tokens below the reference's best, and with ``control`` those of the
    tokens the float8 forward puts first."""
    m = config["model"]
    params = weights.lm_init(m, seed)
    forward = ref_transformer.make_forward(m)
    low = ref_transformer.make_forward(
        m, config["precision"]["control"]) if control else None
    gaps, control_gaps = [], []
    for prompt, served in sample:
        gaps.append(ref_transformer.served_token_gaps(
            forward, params, prompt, served))
        if low is not None:
            control_gaps.append(ref_transformer.served_token_gaps(
                forward, params, prompt, served, control_forward=low))
    return gaps, control_gaps
