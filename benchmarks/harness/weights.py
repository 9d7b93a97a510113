"""Weights and inputs made by the benchmark from ``--seed``.

The program under test and the plain reference are both handed what is made
here; neither makes its own.  Weights are made on the device by one jitted
call, in the type they are trained or served in.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def host_rng(seed, stream=0):
    return np.random.default_rng([int(seed), int(stream)])


# -- transformer LM: the flat {name: array} layout of TransformerLM ---------
def lm_leaf_shapes(m):
    layers, e, f, v = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    # name: (shape, fan_in or None for a scale of ones)
    return {
        "embed": ((v, e), e),
        "blocks.ln1_scale": ((layers, e), None),
        "blocks.ln2_scale": ((layers, e), None),
        "blocks.wqkv": ((layers, e, 3 * e), e),
        "blocks.wo": ((layers, e, e), e),
        "final_ln_scale": ((e,), None),
        "unembed": ((e, v), e),
        "blocks.w_up": ((layers, e, f), e),
        "blocks.w_down": ((layers, f, e), f),
    }


def _lm_leaf(name, spec, key, dtype, index):
    shape, fan_in = spec
    if fan_in is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def lm_init(m, seed, shardings=None):
    """All leaves in one jitted call; ``shardings`` maps names to output
    shardings under a mesh."""
    specs = lm_leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    names = sorted(specs)

    def make(key):
        return {n: _lm_leaf(n, specs[n], key, dtype, i)
                for i, n in enumerate(names)}

    out_sh = None if shardings is None else {n: shardings[n] for n in names}
    return jax.jit(make, out_shardings=out_sh)(key_from_seed(seed))


def lm_init_leaf(m, seed, name, sharding=None):
    """One leaf, the same values ``lm_init`` gives it."""
    specs = lm_leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    index = sorted(specs).index(name)
    fn = jax.jit(lambda key: _lm_leaf(name, specs[name], key, dtype, index),
                 out_shardings=sharding)
    return fn(key_from_seed(seed))


def token_batches(seed, n_batches, batch, seq, vocab):
    """[n, batch, seq + 1] int32 token ids, every row different."""
    rng = host_rng(seed, 1)
    return rng.integers(0, vocab, size=(n_batches, batch, seq + 1),
                        dtype=np.int32)
