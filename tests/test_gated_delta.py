"""``ops/gated_delta.py``: the chunked form against the recurrence taken step
by step, forward and every input's gradient. CPU, float32, seeded."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops.gated_delta import gated_delta, unit_lower_inverse


def recurrence(q, k, v, g, beta, delta_term=True):
    """Item 4 of the op's docstring, one step of the sequence at a time."""
    bsz, t, h, dk = q.shape

    def step(state, at):  # state: (B, H, d_v, d_k)
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhvk,bhk->bhv", state, k_t) if delta_term else 0.0
        state = state + (b_t[..., None] * (v_t - read))[..., None] * k_t[
            ..., None, :]
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t)

    steps = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state = jnp.zeros((bsz, h, v.shape[-1], dk), jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jnp.moveaxis(jax.lax.scan(step, state, steps)[1], 0, 1)


def inputs(seed, t, h=3, dk=8, dv=12, bsz=2, beta_scale=2.0, decay=1.0):
    keys = jax.random.split(jax.random.key(seed), 5)
    norm = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = norm(jax.random.normal(keys[0], (bsz, t, h, dk))) / np.sqrt(dk)
    k = norm(jax.random.normal(keys[1], (bsz, t, h, dk)))
    v = jax.random.normal(keys[2], (bsz, t, h, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (bsz, t, h)))
    beta = beta_scale * jax.nn.sigmoid(
        jax.random.normal(keys[4], (bsz, t, h)))
    return q, k, v, g, beta


CASES = {
    # name: (T, chunk, keyword arguments of ``inputs``)
    "whole_chunks": (32, 8, {}),
    "t_not_a_multiple": (45, 16, {}),
    "t_under_one_chunk": (5, 8, {}),
    "chunk_1": (12, 1, {}),
    "chunk_64": (128, 64, {}),
    # 40 chunks: three segments of ops/gated_delta.SEGMENT, the last padded
    "several_segments": (80, 2, {}),
    "beta_near_2": (48, 16, {}),
    "no_negative_eigenvalue": (32, 8, {"beta_scale": 1.0}),
    "strong_decay": (64, 32, {"decay": 12.0}),
}


def case(name):
    t, chunk, kw = CASES[name]
    if name == "beta_near_2":  # every beta within 1e-3 of 2
        q, k, v, g, _ = inputs(11, t, **kw)
        return (q, k, v, g, jnp.full(g.shape, 1.999)), chunk
    return inputs(7, t, **kw), chunk


@pytest.mark.parametrize("name", sorted(CASES))
def test_chunked_equals_the_recurrence_forward(name):
    args, chunk = case(name)
    with jax.default_matmul_precision("highest"):
        got, low = jax.jit(
            lambda *a: gated_delta(*a, chunk=chunk))(*args)
    want = recurrence(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(low) <= 0.0


@pytest.mark.parametrize("name", sorted(CASES))
def test_chunked_equals_the_recurrence_in_every_gradient(name):
    args, chunk = case(name)
    weights = jax.random.normal(jax.random.key(3), args[2].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weights)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(loss(
            lambda *a: gated_delta(*a, chunk=chunk)[0]), argnums=range(5)))(
                *args)
    want = jax.jit(jax.grad(loss(recurrence), argnums=range(5)))(*args)
    for name_, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-4 * scale,
                                   err_msg=name_)


def test_a_decay_that_a_factorised_form_would_overflow_on():
    """A chunk sums to under -88 nat, so ``exp(-gamma_j)`` is infinite in
    float32: the chunked form, all of whose exponents are never positive,
    stays finite and right, forward and backward, and counts it."""
    (q, k, v, g, beta), chunk = case("strong_decay")
    with jax.default_matmul_precision("highest"):
        out, low = gated_delta(q, k, v, g, beta, chunk=chunk)
        grads = jax.grad(lambda *a: jnp.sum(
            gated_delta(*a, chunk=chunk)[0] ** 2), argnums=range(5))(
                q, k, v, g, beta)
    assert float(low) < -88.0
    chunk_sums = g.reshape(2, -1, chunk, 3).sum(2)
    assert float(low) == pytest.approx(float(chunk_sums.min()), rel=1e-5)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.float32(low)))
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in (out, *grads))
    np.testing.assert_allclose(out, recurrence(q, k, v, g, beta),
                               rtol=2e-4, atol=2e-5)


def test_a_state_not_carried_differs_after_the_first_chunk():
    """The control's fault (``scripts/olmo_hybrid_controls.py``): every chunk
    taken as a sequence of its own, so that each starts from an empty state.
    The op has no knob for it."""
    args, chunk = case("whole_chunks")
    alone = lambda a: a.reshape(-1, chunk, *a.shape[2:])
    with jax.default_matmul_precision("highest"):
        whole, _ = gated_delta(*args, chunk=chunk)
        cut, _ = gated_delta(*map(alone, args), chunk=chunk)
    cut = cut.reshape(whole.shape)
    np.testing.assert_allclose(
        cut, recurrence(*map(alone, args)).reshape(whole.shape), rtol=2e-4,
        atol=2e-5)
    np.testing.assert_allclose(cut[:, :chunk], whole[:, :chunk], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(cut[:, chunk:] - whole[:, chunk:]))) > 1e-2


def test_the_delta_term_matters():
    """Without the read of the state the rule is gated linear attention: the
    control ``scripts/olmo_hybrid_controls.py`` pushes through the cell."""
    args, _ = case("whole_chunks")
    with_term, without = recurrence(*args), recurrence(*args, delta_term=False)
    assert float(jnp.max(jnp.abs(with_term - without))) > 1e-2


def test_bfloat16_operands_keep_float32_decays_and_state():
    (q, k, v, g, beta), chunk = case("chunk_64")
    low = lambda a: a.astype(jnp.bfloat16)
    out, _ = jax.jit(lambda *a: gated_delta(*a, chunk=chunk))(
        low(q), low(k), low(v), g, beta)
    assert out.dtype == jnp.bfloat16
    want = recurrence(q, k, v, g, beta)
    err = jnp.linalg.norm(out.astype(jnp.float32) - want) / jnp.linalg.norm(
        want)
    assert float(err) < 0.03


@pytest.mark.parametrize("c", [1, 2, 16, 64])
def test_the_inverse_by_halves(c):
    lower = jnp.tril(jax.random.normal(jax.random.key(c), (2, 3, c, c)), -1)
    with jax.default_matmul_precision("highest"):
        inv = unit_lower_inverse(0.3 * lower)
        eye = jnp.matmul(inv, jnp.eye(c) + 0.3 * lower)
    np.testing.assert_allclose(eye, jnp.broadcast_to(jnp.eye(c), eye.shape),
                               atol=2e-5)
    assert float(jnp.max(jnp.abs(jnp.triu(inv, 1)))) == 0.0


def test_a_chunk_that_is_no_power_of_two_is_refused():
    (q, k, v, g, beta), _ = case("whole_chunks")
    with pytest.raises(ValueError, match="power of two"):
        gated_delta(q, k, v, g, beta, chunk=12)
