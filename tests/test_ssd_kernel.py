"""The Mamba-2 recurrence's Pallas kernels (``ops/ssd.py``: ``ssd_fwd`` and
``ssd_bwd`` under one ``custom_vjp``) in interpret mode on the CPU, against
the ``jax.numpy`` chunked form they replace where the backend is a TPU and
against the recurrence taken step by step
(``models/reference_nemotron_h.recurrence``).

Small and tiling: 4 heads of 64 channels in 2 groups (``r P`` = 128), a
state of 128, chunks of 128; ``T`` = 256 (whole chunks) and 320 (a padded
last chunk); float32 and bfloat16. One run of each implementation a
``(dtype, T)`` is shared by the cases that read it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import reference_nemotron_h as ref
from mpit_tpu.ops import ssd as ssd_ops

HEADS, GROUPS, P, N, CHUNK = 4, 2, 64, 128, 128
NAMES = ("y", "dx", "ddt", "da", "db", "dc", "dd")
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _inputs(t, dtype, dt_scale=1.0, batch=2):
    ks = jax.random.split(jax.random.key(t), 7)
    x = jax.random.normal(ks[0], (batch, t, HEADS, P)).astype(dtype)
    dt = dt_scale * jax.nn.softplus(
        jax.random.normal(ks[1], (batch, t, HEADS)) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (HEADS,)))
    b, c = ((0.3 * jax.random.normal(k, (batch, t, GROUPS, N))).astype(dtype)
            for k in ks[3:5])
    d = jnp.linspace(0.5, 1.5, HEADS)
    ct = jax.random.normal(ks[6], (batch, t, HEADS, P))
    return (x, dt, a, b, c, d), ct


def _y_and_grads(scan, ins, ct):
    """``(y, dx, ddt, da, db, dc, dd)`` as float32."""
    y, pull = jax.vjp(lambda *v: scan(*v).astype(jnp.float32), *ins)
    return tuple(v.astype(jnp.float32) for v in (y, *pull(ct)))


@functools.cache
def _results(dtype_name, t, dt_scale=1.0):
    ins, ct = _inputs(t, DTYPES[dtype_name], dt_scale)
    per_head = lambda v: jnp.repeat(v, HEADS // GROUPS, axis=2)
    scans = {
        "kernels": lambda *v: ssd_ops.ssd(
            *v, chunk=CHUNK, use_pallas=True)[0],
        "jax.numpy": lambda *v: ssd_ops.ssd(
            *v, chunk=CHUNK, use_pallas=False)[0],
        # float32 throughout, from the same (rounded) inputs
        "recurrence": lambda x, dt, a, b, c, d: ref.recurrence(
            x.astype(jnp.float32), dt, a, per_head(b).astype(jnp.float32),
            per_head(c).astype(jnp.float32), d),
    }
    return {impl: jax.jit(functools.partial(_y_and_grads, scan))(ins, ct)
            for impl, scan in scans.items()}


def _apart(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("t", [256, 320], ids=["whole_chunks", "padded"])
@pytest.mark.parametrize("dtype_name", list(DTYPES))
@pytest.mark.parametrize("against", ["jax.numpy", "recurrence"])
def test_the_kernels_agree_forward_and_in_every_gradient(
        against, dtype_name, t, name):
    """|kernels - other| / |other| over the whole tensor. float32: rounding
    alone. bfloat16: the kernels round what the chunked form rounds (the
    masked ``C B^T``, ``x dt``, the entering state as a product's operand,
    ``y``, every cotangent once), so they stand as far from the float32
    recurrence as it does (read here: 0.3% from the form, 0.4% from the
    recurrence)."""
    results = _results(dtype_name, t)
    got = results["kernels"][NAMES.index(name)]
    want = results[against][NAMES.index(name)]
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0
    limit = 1e-5 if dtype_name == "float32" else 1e-2
    assert _apart(got, want) < limit


@pytest.mark.parametrize("name", NAMES)
def test_a_chunk_decay_far_under_minus_88_is_finite_and_agrees(name):
    """``dt`` x 40: a chunk's log-decay passes float32's exponent range many
    times over, where a form factorised as ``exp(cum_i) exp(-cum_j)``
    overflows; the kernels mask before the exponential, backward too."""
    ins, _ = _inputs(256, jnp.float32, 40.0)
    assert float(ssd_ops.ssd(*ins, chunk=CHUNK, use_pallas=True)[1]) < -88
    results = _results("float32", 256, 40.0)
    got = results["kernels"][NAMES.index(name)]
    assert bool(jnp.isfinite(got).all())
    for against in ("jax.numpy", "recurrence"):
        assert _apart(got, results[against][NAMES.index(name)]) < 1e-4


@pytest.mark.parametrize("t", [256, 320])
def test_log_decay_min_is_the_chunked_forms(t):
    ins, _ = _inputs(t, jnp.bfloat16)
    _, low = ssd_ops.ssd(*ins, chunk=CHUNK, use_pallas=True)
    _, want = ssd_ops.ssd(*ins, chunk=CHUNK, use_pallas=False)
    x, dt, a = ins[:3]
    sums = jnp.pad(dt * a, ((0, 0), (0, -t % CHUNK), (0, 0))).reshape(
        2, -1, CHUNK, HEADS).sum(2)
    assert float(low) == pytest.approx(float(want), rel=1e-6)
    assert float(low) == pytest.approx(float(sums.min()), rel=1e-5)
    assert float(jax.grad(lambda v: ssd_ops.ssd(
        x, v, *ins[2:], chunk=CHUNK, use_pallas=True)[1])(dt).max()) == 0


def test_a_state_not_carried_still_differs_from_the_kernels_result():
    """``carry_state=False`` is the control's fault: it takes the
    ``jax.numpy`` form without being asked, agrees with the kernels inside
    the first chunk and nowhere after it."""
    ins, _ = _inputs(256, jnp.float32)
    y = _results("float32", 256)["kernels"][0]
    cut, _ = ssd_ops.ssd(*ins, chunk=CHUNK, carry_state=False)
    np.testing.assert_allclose(cut[:, :CHUNK], y[:, :CHUNK],
                               rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(cut[:, CHUNK:] - y[:, CHUNK:]).max()) > 1e-2


# -- which form runs ------------------------------------------------------------

def _calls_a_kernel(ins, **kw):
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *v: ssd_ops.ssd(*v, **kw)[0])(*ins))


def _rehearsal_inputs():
    """The rehearsal's shape: 8 heads of 8 channels in 2 groups, a state of
    16, chunks of 8."""
    ks = jax.random.split(jax.random.key(0), 4)
    return (jax.random.normal(ks[0], (1, 32, 8, 8)),
            jax.nn.softplus(jax.random.normal(ks[1], (1, 32, 8))),
            -jnp.arange(1.0, 9.0), jax.random.normal(ks[2], (1, 32, 2, 16)),
            jax.random.normal(ks[3], (1, 32, 2, 16)), jnp.ones(8))


@pytest.mark.parametrize("backend_is_tpu", [False, True])
def test_the_form_is_chosen_from_the_backend_and_the_shape(
        backend_is_tpu, monkeypatch):
    """Unasked: the kernels where the backend is a TPU and the shape tiles,
    the ``jax.numpy`` form on the CPU, at a shape that does not tile and
    for a state not carried."""
    monkeypatch.setattr(ssd_ops, "pallas_supported", lambda: backend_is_tpu)
    tiling, _ = _inputs(256, jnp.bfloat16, batch=1)
    assert _calls_a_kernel(tiling, chunk=CHUNK) == backend_is_tpu
    assert not _calls_a_kernel(tiling, chunk=CHUNK, carry_state=False)
    assert not _calls_a_kernel(tiling, chunk=8)
    assert not _calls_a_kernel(_rehearsal_inputs(), chunk=8)
    assert _calls_a_kernel(tiling, chunk=CHUNK, use_pallas=True)
    assert not _calls_a_kernel(tiling, chunk=CHUNK, use_pallas=False)


@pytest.mark.parametrize("case", ["chunk", "head_dim_and_state", "no_carry"])
def test_asking_for_the_kernels_where_they_cannot_run_raises_by_name(case):
    tiling, _ = _inputs(256, jnp.bfloat16, batch=1)
    ins, kw = {
        "chunk": (tiling, {"chunk": 8}),
        "head_dim_and_state": (_rehearsal_inputs(), {"chunk": 128}),
        "no_carry": (tiling, {"chunk": CHUNK, "carry_state": False}),
    }[case]
    with pytest.raises(ValueError, match="ssd: the kernels want"):
        ssd_ops.ssd(*ins, use_pallas=True, **kw)


def test_tiles_is_the_rule_the_docstring_states():
    assert ssd_ops.tiles(128, 64, 8, 64, 128)  # the published shape
    assert ssd_ops.tiles(256, 4, 2, 64, 128)
    assert not ssd_ops.tiles(8, 8, 2, 8, 16)  # the rehearsal's
    assert not ssd_ops.tiles(64, 64, 8, 64, 128)  # chunk
    assert not ssd_ops.tiles(128, 64, 8, 64, 64)  # state
    assert not ssd_ops.tiles(128, 8, 8, 64, 128)  # one head of 64 a group
