"""The gated delta rule's Pallas kernels (``ops/gated_delta.py``:
``delta_fwd`` and ``delta_bwd`` under one ``custom_vjp``) in interpret mode on
the CPU, against the ``jax.numpy`` chunked form they replace where the backend
is a TPU and against the recurrence taken step by step
(``models/reference_olmo_hybrid.recurrence``), forward and in every gradient.

Small and tiling: 2 heads at the cell's head sizes (keys of 96, values of
192), chunks of 64; ``T`` = 128 (whole chunks) and 100 (a padded last chunk);
float32 and bfloat16 operands (decays and state float32 in both). One run of
each implementation a case is shared by the tests that read it.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from mpit_tpu.models import reference_olmo_hybrid as ref
from mpit_tpu.ops import gated_delta as delta_ops

HEADS, DK, DV, CHUNK = 2, 96, 192, 64
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
#: name: (keyword arguments of ``_inputs``)
CASES = {
    "plain": {},
    # every key shares a component with every other (cosine 0.5 between
    # steps) and beta lies in 1.5 to 2: where the product form of the
    # inverse returns NaN (PERF.md section 6, PR 34)
    "correlated_keys": {"correlated": True},
    # a chunk's log decay passes float32's exponent range: a form factorised
    # as exp(gamma_i) exp(-gamma_j) overflows
    "strong_decay": {"decay": 12.0},
}


def _inputs(t, dtype, correlated=False, decay=1.0, batch=2):
    ks = jax.random.split(jax.random.key(t), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    k = unit(jax.random.normal(ks[1], (batch, t, HEADS, DK)))
    if correlated:
        k = unit(k + unit(jax.random.normal(ks[6], (batch, 1, HEADS, DK))))
    q = unit(jax.random.normal(ks[0], (batch, t, HEADS, DK))) * DK ** -0.5
    v = jax.random.normal(ks[2], (batch, t, HEADS, DV))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (batch, t, HEADS)))
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(ks[4], (batch, t, HEADS))
        + (2.0 if correlated else 0.0))
    ct = jax.random.normal(ks[5], (batch, t, HEADS, DV))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), ct


def _o_and_grads(op, ins, ct):
    """``(o, dq, dk, dv, dg, dbeta)`` as float32."""
    o, pull = jax.vjp(lambda *a: op(*a).astype(jnp.float32), *ins)
    return tuple(a.astype(jnp.float32) for a in (o, *pull(ct)))


@functools.cache
def _results(dtype_name, t, case="plain"):
    ins, ct = _inputs(t, DTYPES[dtype_name], **CASES[case])
    ops = {
        "kernels": lambda *a: delta_ops.gated_delta(
            *a, chunk=CHUNK, use_pallas=True)[0],
        "jax.numpy": lambda *a: delta_ops.gated_delta(
            *a, chunk=CHUNK, use_pallas=False)[0],
        # float32 throughout, from the same (rounded) inputs
        "recurrence": lambda *a: ref.recurrence(
            *(x.astype(jnp.float32) for x in a)),
    }
    with jax.default_matmul_precision("highest"):
        return {impl: jax.jit(functools.partial(_o_and_grads, op))(ins, ct)
                for impl, op in ops.items()}


def _apart(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("t", [128, 100], ids=["whole_chunks", "padded"])
@pytest.mark.parametrize("dtype_name", list(DTYPES))
@pytest.mark.parametrize("against", ["jax.numpy", "recurrence"])
def test_the_kernels_agree_forward_and_in_every_gradient(
        against, dtype_name, t, name):
    """|kernels - other| / |other| over the whole tensor. float32: rounding
    alone. bfloat16: the kernels round what the chunked form rounds (``T
    diag(beta)``, ``W``, ``V_new``, the decayed ``Q K^T``, the entering state
    as a product's operand, ``o``, every operand's cotangent once), so they
    stand as far from the float32 recurrence as it does (read here: up to
    0.4% from either)."""
    results = _results(dtype_name, t)
    got = results["kernels"][NAMES.index(name)]
    want = results[against][NAMES.index(name)]
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0
    limit = 1e-5 if dtype_name == "float32" else 1e-2
    assert _apart(got, want) < limit


@pytest.mark.parametrize("name", NAMES)
def test_correlated_keys_with_beta_up_to_2(name):
    """Where ``A``'s entries are of order 1 and the product form's powers
    grow before they vanish: the kernel's substitution in 16 x 16 blocks and
    halves above them pass through blocks of the true inverse alone."""
    ins, _ = _inputs(128, jnp.float32, correlated=True)
    k = ins[1]
    cosines = jnp.einsum("bthd,bshd->bhts", k, k)
    assert 0.4 < float(jnp.median(cosines)) < 0.6
    assert 1.9 < float(ins[4].max()) <= 2.0
    results = _results("float32", 128, "correlated_keys")
    got = results["kernels"][NAMES.index(name)]
    assert bool(jnp.isfinite(got).all())
    for against in ("jax.numpy", "recurrence"):
        assert _apart(got, results[against][NAMES.index(name)]) < 1e-5


@pytest.mark.parametrize("name", NAMES)
def test_a_chunk_decay_far_under_minus_88_is_finite_and_agrees(name):
    """The kernels mask before the exponential, backward too, and divide by
    no decay."""
    ins, _ = _inputs(128, jnp.float32, decay=12.0)
    assert float(delta_ops.gated_delta(
        *ins, chunk=CHUNK, use_pallas=True)[1]) < -88
    results = _results("float32", 128, "strong_decay")
    got = results["kernels"][NAMES.index(name)]
    assert bool(jnp.isfinite(got).all())
    for against in ("jax.numpy", "recurrence"):
        assert _apart(got, results[against][NAMES.index(name)]) < 1e-4


def test_bfloat16_operands_keep_float32_decays_and_state():
    """``o`` comes back in ``v``'s dtype; the entering states the forward
    hands the backward are float32, and so are the cotangents of ``g`` and
    ``beta``."""
    ins, ct = _inputs(128, jnp.bfloat16, batch=1)
    kernels = lambda *a: delta_ops.gated_delta(
        *a, chunk=CHUNK, use_pallas=True)[0]
    o, pull = jax.vjp(kernels, *ins)
    grads = pull(ct.astype(o.dtype))
    assert [a.dtype for a in (o, *grads)] == [jnp.bfloat16] * 4 + [
        jnp.float32] * 2
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(kernels, *a)[0])(*ins))
    assert f"f32[1,2,{HEADS},{DK},{DV}]" in text  # two chunks' states


@pytest.mark.parametrize("t", [128, 100])
def test_log_decay_min_is_the_chunked_forms(t):
    ins, _ = _inputs(t, jnp.bfloat16)
    _, low = delta_ops.gated_delta(*ins, chunk=CHUNK, use_pallas=True)
    _, want = delta_ops.gated_delta(*ins, chunk=CHUNK, use_pallas=False)
    g = ins[3]
    sums = jnp.pad(g, ((0, 0), (0, -t % CHUNK), (0, 0))).reshape(
        2, -1, CHUNK, HEADS).sum(2)
    assert float(low) == pytest.approx(float(want), rel=1e-6)
    assert float(low) == pytest.approx(float(sums.min()), rel=1e-5)
    assert float(jnp.abs(jax.grad(lambda v: delta_ops.gated_delta(
        *ins[:3], v, ins[4], chunk=CHUNK, use_pallas=True)[1])(g)).max()) == 0


@pytest.mark.parametrize("c", [16, 32, 64, 128])
def test_the_inverse_in_vmem_is_the_inverse_by_halves(c):
    """The kernels' solve alone (substitution inside 16 x 16 blocks, halves
    above) as plain array code, against ``unit_lower_inverse``."""
    lower = 0.3 * jnp.tril(jax.random.normal(jax.random.key(c), (c, c)), -1)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    with jax.default_matmul_precision("highest"):
        got = delta_ops._inverses_in_vmem([lower])[0]
        want = delta_ops.unit_lower_inverse(lower)
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


# -- which form runs ------------------------------------------------------------

def _calls_a_kernel(ins, **kw):
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *a: delta_ops.gated_delta(*a, **kw)[0])(*ins))


def _rehearsal_inputs():
    """The rehearsal's shape: 2 heads, keys of 8, values of 12, chunks of
    8."""
    ks = jax.random.split(jax.random.key(0), 5)
    return (jax.random.normal(ks[0], (1, 32, 2, 8)),
            jax.random.normal(ks[1], (1, 32, 2, 8)),
            jax.random.normal(ks[2], (1, 32, 2, 12)),
            -jax.nn.softplus(jax.random.normal(ks[3], (1, 32, 2))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (1, 32, 2))))


@pytest.mark.parametrize("backend_is_tpu", [False, True])
def test_the_form_is_chosen_from_the_backend_and_the_shape(
        backend_is_tpu, monkeypatch):
    """Unasked: the kernels where the backend is a TPU and the shape tiles,
    the ``jax.numpy`` form on the CPU and at a shape that does not tile."""
    monkeypatch.setattr(
        delta_ops, "pallas_supported", lambda: backend_is_tpu)
    tiling, _ = _inputs(128, jnp.bfloat16, batch=1)
    assert _calls_a_kernel(tiling, chunk=CHUNK) == backend_is_tpu
    assert _calls_a_kernel(tiling, chunk=16) == backend_is_tpu
    assert not _calls_a_kernel(tiling, chunk=8)
    assert not _calls_a_kernel(_rehearsal_inputs(), chunk=8)
    assert not _calls_a_kernel(_rehearsal_inputs(), chunk=16)
    assert _calls_a_kernel(tiling, chunk=CHUNK, use_pallas=True)
    assert not _calls_a_kernel(tiling, chunk=CHUNK, use_pallas=False)


@pytest.mark.parametrize("case", ["chunk", "key_dim", "value_dim"])
def test_asking_for_the_kernels_where_they_cannot_run_raises_by_name(case):
    q, k, v, g, beta = _inputs(128, jnp.bfloat16, batch=1)[0]
    ins, chunk, named = {
        "chunk": ((q, k, v, g, beta), 8, "got 8"),
        "key_dim": ((q[..., :8], k[..., :8], v, g, beta), CHUNK, "d_k=8"),
        "value_dim": ((q, k, v[..., :12], g, beta), CHUNK, "d_v=12"),
    }[case]
    with pytest.raises(ValueError, match="gated_delta: the kernels want"
                       ) as raised:
        delta_ops.gated_delta(*ins, chunk=chunk, use_pallas=True)
    assert named in str(raised.value)


def test_tiles_is_the_rule_the_docstring_states():
    assert delta_ops.tiles(64, 96, 192)  # the published shape
    assert delta_ops.tiles(16, 16, 16)
    assert delta_ops.tiles(128, 128, 256)
    assert not delta_ops.tiles(8, 8, 12)  # the rehearsal's
    assert not delta_ops.tiles(8, 96, 192)  # chunk
    assert not delta_ops.tiles(256, 96, 192)
    assert not delta_ops.tiles(64, 8, 192)  # keys
    assert not delta_ops.tiles(64, 96, 12)  # values


def test_heads_a_step_divide_the_heads():
    assert delta_ops._heads_a_step(30) == delta_ops._HEADS
    for heads in (1, 2, 7, 8, 30, 64):
        step = delta_ops._heads_a_step(heads)
        assert heads % step == 0 and 1 <= step <= delta_ops._HEADS


# -- through the model ------------------------------------------------------------

@pytest.mark.parametrize("remat", [False, True])
def test_a_model_takes_the_kernels_where_the_backend_offers_them(
        remat, monkeypatch):
    """Two gated-delta-rule layers at a tiling shape (2 heads, keys of 16,
    values of 32, chunks of 16, 40 tokens: a padded last chunk): with the
    backend's offer patched in (interpreted here) the model's loss and every
    gradient are the ``jax.numpy`` path's, with and without the block's
    remat, and no option of the model was touched."""
    from mpit_tpu.models.transformer import TransformerLM

    arch = {
        "norm_at": "output", "hidden_size": 32, "intermediate_size": 48,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 2, "layer_types": ["linear_attention"] * 2,
        "linear_num_key_heads": 2, "linear_num_value_heads": 2,
        "linear_key_head_dim": 16, "linear_value_head_dim": 32,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "linear_chunk_size": 16, "rope_parameters": {"rope_theta": None},
    }
    model = TransformerLM(vocab_size=61, arch=arch, remat=remat,
                          compute_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 61)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]

    def loss_and_grads():
        fn = lambda p: model.loss_with_counters(p, tokens, tokens)[0]
        text = str(jax.make_jaxpr(jax.grad(fn))(params))
        return jax.jit(jax.value_and_grad(fn))(params), "pallas_call" in text

    (want, want_grads), kernel = loss_and_grads()
    assert not kernel
    monkeypatch.setattr(delta_ops, "pallas_supported", lambda: True)
    (got, got_grads), kernel = loss_and_grads()
    assert kernel
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_grads)[0],
                            jax.tree.leaves(want_grads)):
        assert _apart(a, b) < 1e-4, jax.tree_util.keystr(path)
