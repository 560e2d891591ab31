"""bf16 vocab-head quality guard.

The LM vocab heads compute with compute-dtype operands and f32
accumulation (transformer.py / lstm.py ``_head``). The equivalence
suites pin ``compute_dtype=float32`` configs, where that choice is
bit-identical — so the shipped bf16 path's numerical effect on training
was covered by no test. This file closes that hole with a synthetic
train-and-eval parity check, isolated to the HEAD via the
``head_dtype`` override: the trunk stays f32 in both arms, so the only
difference is the head matmul's operand precision (forward AND the
gradients that flow through it).

Tolerance: final losses within ``TOL_LOSS`` after ``STEPS`` steps on a
learnable task, with both arms required to actually learn (no vacuous
pass). The old LSTM recipe — logits *quantized to bf16 on output* —
fails the logit-precision bound asserted here (that is the regression
this guard exists to catch); bf16 operands with f32 accumulation pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mpit_tpu.models.lstm import LSTMLM
from mpit_tpu.models.transformer import TransformerLM

V, T, B = 512, 32, 32
STEPS = 120
TOL_LOSS = 0.05  # |final f32-head loss - final bf16-head loss|


def _data(seed, n=B * 4):
    """Learnable synthetic LM: next token = (3*t + 7) mod V, with the
    sequence start randomized — a task the models drive to near-zero
    loss in ~100 steps, so a head-precision problem shows as a loss
    gap, not as noise."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, V, (n, 1))
    steps = np.arange(T + 1)[None, :]
    seq = (starts + 3 * steps * (starts % 5 + 1)) % V
    return seq[:, :T].astype(np.int32), seq[:, 1:].astype(np.int32)


def _train(model, seed=0):
    x, y = _data(seed=1)
    params = model.init(jax.random.key(seed), x[:2])["params"]
    opt = optax.adam(3e-3)
    ost = opt.init(params)

    def loss_fn(p, xb, yb):
        logits = model.apply({"params": p}, xb)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yb
        ).mean()

    @jax.jit
    def step(p, o, xb, yb):
        loss, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        up, o = opt.update(g, o)
        return optax.apply_updates(p, up), o, loss

    first = None
    for i in range(STEPS):
        j = (i * B) % len(x)
        params, ost, loss = step(params, ost, x[j:j + B], y[j:j + B])
        if first is None:
            first = float(loss)
    xe, ye = _data(seed=2, n=B)
    eval_loss = float(loss_fn(params, xe, ye))
    return first, float(loss), eval_loss, params


def _transformer(**kw):
    return TransformerLM(
        vocab_size=V, num_layers=2, d_model=64, num_heads=4, max_len=T,
        compute_dtype=jnp.float32, **kw,
    )


def _lstm(**kw):
    return LSTMLM(
        vocab_size=V, embed_dim=32, hidden=64, num_layers=1,
        compute_dtype=jnp.float32, **kw,
    )


@pytest.mark.parametrize("family", ["transformer", "lstm"])
def test_bf16_head_trains_to_f32_head_quality(family):
    """Same seed, same data, f32 trunk: a bf16-operand/f32-accum head
    must land within TOL_LOSS of the all-f32 head on BOTH final train
    loss and held-out eval loss — and both arms must actually learn."""
    build = _transformer if family == "transformer" else _lstm
    first, f32_final, f32_eval, _ = _train(build())
    _, bf16_final, bf16_eval, _ = _train(build(head_dtype=jnp.bfloat16))
    assert f32_final < 0.5 * first, "reference arm failed to learn"
    assert bf16_final < 0.5 * first, "bf16-head arm failed to learn"
    assert abs(f32_final - bf16_final) < TOL_LOSS, (
        f"{family}: bf16 head drifted {abs(f32_final - bf16_final):.4f} "
        f"in train loss (tolerance {TOL_LOSS})"
    )
    assert abs(f32_eval - bf16_eval) < TOL_LOSS, (
        f"{family}: bf16 head drifted {abs(f32_eval - bf16_eval):.4f} "
        f"in eval loss (tolerance {TOL_LOSS})"
    )


@pytest.mark.parametrize("family", ["transformer", "lstm"])
def test_head_dtype_none_is_compute_dtype(family):
    """The override's identity contract: head_dtype=f32 on an f32 model
    is bit-identical to the default — the A/B above really isolates the
    head, and adding the knob changed nothing for every existing
    config."""
    build = _transformer if family == "transformer" else _lstm
    x, _ = _data(seed=3, n=4)
    m0, m1 = build(), build(head_dtype=jnp.float32)
    params = m0.init(jax.random.key(0), x)["params"]
    a = m0.apply({"params": params}, x)
    b = m1.apply({"params": params}, x)
    assert jnp.array_equal(a, b)


def test_accumulation_beats_output_quantization():
    """Why f32 accumulation is the contract: logits QUANTIZED to bf16 on
    output (the old LSTM recipe) violate the precision this guard's
    tolerance encodes — the shipped head's error vs an all-f32 head
    stays well inside the error output-quantization adds on top."""
    model = _transformer()
    x, _ = _data(seed=4, n=8)
    params = model.init(jax.random.key(0), x)["params"]
    f32_logits = model.apply({"params": params}, x)
    shipped = _transformer(head_dtype=jnp.bfloat16).apply(
        {"params": params}, x
    )
    old_recipe = f32_logits.astype(jnp.bfloat16).astype(jnp.float32)
    shipped_err = float(jnp.max(jnp.abs(shipped - f32_logits)))
    quant_err = float(jnp.max(jnp.abs(old_recipe - f32_logits)))
    # the shipped path keeps f32 output resolution; quantization floors
    # the error at bf16's 8-bit mantissa regardless of accumulation
    assert shipped.dtype == jnp.float32
    assert shipped_err < 2.0 * quant_err  # comparable forward error...
    probs_f32 = jax.nn.softmax(f32_logits)
    probs_ship = jax.nn.softmax(shipped)
    probs_old = jax.nn.softmax(old_recipe)
    # ...but the distribution the model SAMPLES from is strictly more
    # faithful through the shipped head than through output quantization
    d_ship = float(jnp.max(jnp.abs(probs_ship - probs_f32)))
    d_old = float(jnp.max(jnp.abs(probs_old - probs_f32)))
    assert d_ship <= d_old
