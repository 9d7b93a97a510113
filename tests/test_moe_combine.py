"""The expert layer's combine (``parallel.moe._combine``) and the dispatch's
transpose (``_to_sorted``'s backward): one operation each, its backward
written by hand, against ``jax.grad`` of the plain form -- ``out[inverse]
.reshape(S, k, E)``, the einsum over the slots, ``tokens[order // k]`` --
which is what the layer ran before and what autodiff transposed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import moe

S, E, N = 12, 16, 8
KS = [1, 2, 6]
# which experts the layer holds, and where the router sends the tokens
ROUTINGS = {
    "all_held": (tuple(range(N)), None),
    # experts 1, 4, 6 absent: their pairs sort behind the groups
    "held_subset": ((5, 0, 2, 7, 3), None),
    # nobody chooses expert 2 or 3: two empty groups among the held
    "empty_group": (tuple(range(N)), (0, 1, 4, 5, 6, 7)),
    "one_expert_first": (tuple(range(N)), "one"),
}


def pairs(k, routing, dtype=jnp.float32, seed=0):
    """The sorted rows of ``S k`` (token, slot) pairs as ``expert_layer``
    makes them: ``(out [P, E], weights [S, k], order, inverse)``, the rows
    behind the groups zero as the grouped product leaves them."""
    held, chosen = ROUTINGS[routing]
    rng = np.random.RandomState(seed)
    if chosen == "one":                  # every token's first slot on 3
        experts = np.stack([np.r_[3, rng.permutation(
            [e for e in range(N) if e != 3])[:k - 1]] for _ in range(S)])
    else:
        pool = np.asarray(chosen or range(N))
        experts = np.stack([rng.permutation(pool)[:k] for _ in range(S)])
    experts = experts.astype(np.int32).reshape(S, k)
    place = np.full((N,), len(held), np.int32)
    place[list(held)] = np.arange(len(held))
    key = place[experts.reshape(S * k)]
    order = np.argsort(key, kind="stable").astype(np.int32)
    inverse = np.argsort(order).astype(np.int32)
    live = int((key < len(held)).sum())
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    out = jax.random.normal(ks[0], (S * k, E)).astype(dtype)
    out = out * (jnp.arange(S * k) < live)[:, None].astype(dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (S, k)), axis=-1)
    return out, weights, jnp.asarray(order), jnp.asarray(inverse), live


def plain_combine(out, weights, inverse):
    k = weights.shape[1]
    slots = out[inverse].reshape(-1, k, out.shape[-1])
    return jnp.einsum("ske,sk->se", slots.astype(jnp.float32),
                      weights).astype(out.dtype)


CASES = [(k, r) for k in KS for r in ROUTINGS]
IDS = ["k%d-%s" % c for c in CASES]


@pytest.mark.parametrize("k,routing", CASES, ids=IDS)
def test_combine_and_its_gradients_are_the_plain_forms(k, routing):
    out, weights, order, inverse, live = pairs(k, routing)
    assert routing != "held_subset" or 0 < live < S * k
    probe = jax.random.normal(jax.random.PRNGKey(7), (S, E))

    def loss(fn):
        return lambda out, weights: jnp.sum(fn(out, weights) * probe)

    def ours(out, weights):
        return moe._combine(out, weights, order, inverse)

    def plain(out, weights):
        return plain_combine(out, weights, inverse)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(out, weights), plain(out, weights),
                                   rtol=1e-6, atol=1e-6)
        got = jax.grad(loss(ours), argnums=(0, 1))(out, weights)
        want = jax.grad(loss(plain), argnums=(0, 1))(out, weights)
    np.testing.assert_array_equal(got[0], want[0])          # d_out
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)
    assert got[1].shape == (S, k) and float(jnp.abs(got[1]).max()) > 0
    # a slot whose expert is absent reads a zero row: no weight gradient
    absent = np.asarray(inverse).reshape(S, k) >= live
    assert (np.asarray(got[1])[absent] == 0).all()


@pytest.mark.parametrize("k,routing", CASES, ids=IDS)
def test_the_dispatchs_token_gradient_is_the_plain_gathers(k, routing):
    _out, _w, order, inverse, _live = pairs(k, routing)
    tokens = jax.random.normal(jax.random.PRNGKey(3), (S, E))
    probe = jax.random.normal(jax.random.PRNGKey(4), (S * k, E))
    np.testing.assert_array_equal(
        moe._to_sorted(tokens, order, inverse, k), tokens[order // k])
    got = jax.grad(lambda t: jnp.sum(
        moe._to_sorted(t, order, inverse, k) * probe))(tokens)
    want = jax.grad(lambda t: jnp.sum(t[order // k] * probe))(tokens)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k,routing", CASES, ids=IDS)
def test_d_out_in_bfloat16_is_the_old_forms_to_the_bit(k, routing):
    """The old form's ``d_out``: autodiff's transpose of the einsum, a
    float32 ``[S, k, E]``, rounded and permuted by ``order``."""
    out, weights, order, inverse, _live = pairs(k, routing, jnp.bfloat16)
    probe = jax.random.normal(jax.random.PRNGKey(8), (S, E)
                              ).astype(jnp.bfloat16)
    got = jax.grad(lambda o: jnp.sum(
        (moe._combine(o, weights, order, inverse) * probe
         ).astype(jnp.float32)))(out)
    want = jax.grad(lambda o: jnp.sum(
        (plain_combine(o, weights, inverse) * probe
         ).astype(jnp.float32)))(out)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
