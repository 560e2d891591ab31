"""End-to-end sync allreduce DP: the TPU-native `ptest`-class smoke test
(SURVEY.md §4: keep an MNIST e2e as the canonical integration test, plus the
unit checks the reference lacked)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import mpit_tpu
from mpit_tpu.data import Batches, load_mnist
from mpit_tpu.models import LeNet
from mpit_tpu.parallel import DataParallelTrainer


@pytest.fixture
def mnist():
    return load_mnist(synthetic_train=2048, synthetic_test=512)


def test_grad_averaging_matches_single_worker(topo8):
    """8-worker DP on a global batch must equal 1 worker on the same batch:
    the collective average reproduces the full-batch gradient."""
    model = LeNet(compute_dtype=jnp.float32)
    opt = optax.sgd(0.1)
    rng = np.random.default_rng(0)
    x = rng.uniform(0, 1, (16, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, 16).astype(np.int32)

    t8 = DataParallelTrainer(model, opt, topo8, donate_state=False)
    s8 = t8.init_state(jax.random.key(0), x[:2])
    s8_next, m8 = t8.step(s8, x, y)

    mpit_tpu.finalize()
    topo1 = mpit_tpu.init(num_workers=1)
    t1 = DataParallelTrainer(model, opt, topo1, donate_state=False)
    s1 = t1.init_state(jax.random.key(0), x[:2])
    s1_next, m1 = t1.step(s1, x, y)

    np.testing.assert_allclose(
        float(m8["loss"]), float(m1["loss"]), rtol=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        s8_next.params,
        s1_next.params,
    )


def test_local_value_and_grad_is_what_the_step_averages(topo8):
    """``trainer._local_vg`` is the step's own value-and-gradient: the
    benchmark's Laguna driver jits it to hold the system's gradient
    against the plain reference's. On the whole batch it gives the
    gradient the step's ``pmean`` of the shards' gives."""
    model = LeNet(compute_dtype=jnp.float32)
    rng = np.random.default_rng(2)
    x = rng.uniform(0, 1, (16, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, 16).astype(np.int32)
    tr = DataParallelTrainer(model, optax.sgd(0.1), topo8, donate_state=False)
    state = tr.init_state(jax.random.key(0), x[:2])
    loss, grads = jax.jit(tr._local_vg)(state.params, x, y)
    stepped, metrics = tr.step(state, x, y)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss), rtol=1e-5)
    jax.tree.map(
        lambda p, g, q: np.testing.assert_allclose(
            np.asarray(p) - 0.1 * np.asarray(g), np.asarray(q), atol=2e-5
        ),
        state.params, grads, stepped.params,
    )


def test_grad_accumulation_matches_full_batch(topo8):
    """accum_steps=4 on the same global batch must reproduce the
    unaccumulated step exactly (equal slice sizes, mean losses, no batch
    statistics in any model here) — accumulation is a memory knob, not a
    math change."""
    model = LeNet(compute_dtype=jnp.float32)
    opt = optax.sgd(0.1, momentum=0.9)
    rng = np.random.default_rng(1)
    x = rng.uniform(0, 1, (64, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, 64).astype(np.int32)

    results = {}
    for accum in (1, 4):
        tr = DataParallelTrainer(
            model, opt, topo8, donate_state=False, accum_steps=accum
        )
        st = tr.init_state(jax.random.key(0), x[:2])
        losses = []
        for _ in range(3):
            st, m = tr.step(st, x, y)
            losses.append(float(m["loss"]))
        results[accum] = (
            losses, jax.tree.map(np.asarray, jax.device_get(st.params))
        )
    np.testing.assert_allclose(results[4][0], results[1][0], rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5),
        results[4][1], results[1][1],
    )
    # divisibility: per-worker batch of 8 % accum 3 != 0
    tr3 = DataParallelTrainer(
        model, opt, topo8, donate_state=False, accum_steps=3
    )
    st3 = tr3.init_state(jax.random.key(0), x[:2])
    with pytest.raises(ValueError, match="accum_steps"):
        tr3.step(st3, x, y)


@pytest.mark.slow
def test_sync_dp_trains_mnist(topo8, mnist):
    x_tr, y_tr, x_te, y_te = mnist
    model = LeNet(compute_dtype=jnp.float32)
    trainer = DataParallelTrainer(model, optax.adam(1e-3), topo8)
    state = trainer.init_state(jax.random.key(0), x_tr[:2])
    batches = Batches(x_tr, y_tr, global_batch=256, seed=0)

    acc0, _ = trainer.evaluate(state, x_te, y_te, batch=256)
    state, metrics = trainer.fit(batches, state, epochs=3)
    acc1, loss1 = trainer.evaluate(state, x_te, y_te, batch=256)

    assert acc0 < 0.3  # untrained ~ chance
    assert acc1 > 0.9, f"sync DP failed to learn: acc={acc1}, loss={loss1}"


def test_step_counts_and_batch_divisibility(topo8, mnist):
    x_tr, y_tr, *_ = mnist
    model = LeNet(compute_dtype=jnp.float32)
    trainer = DataParallelTrainer(model, optax.sgd(0.01), topo8)
    state = trainer.init_state(jax.random.key(0), x_tr[:2])
    state, _ = trainer.step(state, x_tr[:16], y_tr[:16])
    assert int(state.step) == 1
    with pytest.raises(ValueError, match="not divisible"):
        trainer.step(state, x_tr[:17], y_tr[:17])


def _init_cases():
    from mpit_tpu.models import MLP
    from mpit_tpu.models.transformer import TransformerLM

    rng = np.random.default_rng(0)
    images = rng.uniform(0, 1, (2, 28, 28, 1)).astype(np.float32)
    tokens = rng.integers(0, 31, (2, 16)).astype(np.int32)
    lm = TransformerLM(
        vocab_size=31, num_layers=2, d_model=32, num_heads=2, max_len=16
    )
    # rtol: 0 = equal to the bit. The LM's two embedding tables are drawn
    # as normal * stddev, which the jitted program fuses: one ulp apart
    return [
        pytest.param(LeNet(), images, 0.0, id="lenet"),
        pytest.param(MLP(), images, 0.0, id="mlp"),
        pytest.param(lm, tokens, 2.0 ** -22, id="transformer"),
    ]


@pytest.mark.parametrize("model,sample,rtol", _init_cases())
def test_jitted_init_state_equals_eager(topo8, model, sample, rtol):
    """``jit_init`` changes how the state is made, not what it is: same
    key, same sample, the same leaves, replicated alike."""
    states = [
        DataParallelTrainer(
            model, optax.adamw(1e-3), topo8, jit_init=jit_init
        ).init_state(jax.random.key(3), sample)
        for jit_init in (False, True)
    ]
    eager, jitted = map(jax.tree.leaves, states)
    assert jax.tree.structure(states[0]) == jax.tree.structure(states[1])
    for a, b in zip(eager, jitted):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        assert a.sharding.is_fully_replicated
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=0
        )


def _tiny_lm():
    from mpit_tpu.models.transformer import TransformerLM

    # widths no other test uses, so that no earlier program is cached
    lm = TransformerLM(
        vocab_size=29, num_layers=2, d_model=24, num_heads=2, max_len=12
    )
    return lm, np.random.default_rng(1).integers(0, 29, (2, 12)).astype(
        np.int32
    )


def _own_buffers(tree) -> bool:
    ptrs = [
        s.data.unsafe_buffer_pointer()
        for a in jax.tree.leaves(tree)
        for s in a.addressable_shards
    ]
    return len(set(ptrs)) == len(ptrs)


@pytest.fixture(params=[1, 8], ids=["w1", "w8"])
def workers(request):
    return mpit_tpu.init(num_workers=request.param)


@pytest.mark.parametrize("model,sample,rtol", _init_cases())
def test_easgd_init_state_is_the_eager_construction(
    workers, model, sample, rtol
):
    """EASGD makes its state in one jitted program: what an eager
    construction makes (``model.init``, ``optimizer.init``, a broadcast
    over workers), each leaf born with its sharding and its own buffer
    (the round donates them all)."""
    from mpit_tpu.parallel import EASGDTrainer
    from mpit_tpu.parallel.easgd import EASGDState

    opt, w = optax.adamw(1e-3), workers.num_workers
    state = EASGDTrainer(model, opt, workers).init_state(
        jax.random.key(3), sample
    )
    params = model.init(jax.random.key(3), jnp.asarray(sample))["params"]
    stack = lambda t: jax.tree.map(
        lambda a: np.broadcast_to(a, (w, *a.shape)), t
    )
    eager = EASGDState(
        worker_params=stack(params), worker_opt=stack(opt.init(params)),
        center=params, round=np.zeros((), np.int32),
    )
    assert jax.tree.structure(state) == jax.tree.structure(eager)
    for part in ("worker_params", "worker_opt", "center", "round"):
        made, want = getattr(state, part), getattr(eager, part)
        for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=rtol, atol=0
            )
            if part.startswith("worker"):
                assert a.sharding.is_equivalent_to(
                    workers.worker_sharding(), a.ndim
                )
            else:
                assert a.sharding.is_fully_replicated
    assert _own_buffers(state)


def test_easgd_init_state_takes_given_params(workers):
    """With ``params`` and no model, the program takes them as its
    argument; the round's first call donates the state, the center that
    holds the argument's values and AdamW's two zero moments included,
    and leaves the caller's ``params`` alive."""
    from mpit_tpu.parallel import EASGDTrainer

    w = workers.num_workers
    tr = EASGDTrainer(
        model=None, optimizer=optax.adamw(0.1), topo=workers,
        loss_fn=lambda p, x, y: jnp.sum((p["p"] - x[0]) ** 2), tau=2,
    )
    params = {"p": jnp.arange(2.0)}
    state = tr.init_state(None, params=params)
    np.testing.assert_array_equal(state.center["p"], [0.0, 1.0])
    np.testing.assert_array_equal(
        state.worker_params["p"], np.tile([0.0, 1.0], (w, 1))
    )
    assert _own_buffers((state, params))
    state, metrics = tr.step(
        state, np.ones((2, w, 2), np.float32), np.zeros((2, w), np.float32)
    )
    assert np.isfinite(float(metrics["loss"])) and int(state.round) == 1
    np.testing.assert_array_equal(params["p"], [0.0, 1.0])


def test_easgd_init_state_compiles_one_program(topo8):
    """The engagement counter: ``init_state`` compiles the state's one
    program, not the dozens of an op-by-op ``model.init`` (the
    ``backend_compile_duration`` events ``benchmark/lib/timing.py`` sums
    into ``compile_s``). The eager construction beside it is the control
    that the counter sees them."""
    import jax.monitoring as mon

    from mpit_tpu.parallel import EASGDTrainer

    lm, tokens = _tiny_lm()
    opt = optax.adamw(1e-3)
    tr = EASGDTrainer(lm, opt, topo8)
    key = jax.random.key(5)
    seen = []

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(seconds)

    def programs(fn) -> int:
        seen.clear()
        mon.register_event_duration_secs_listener(on_duration)
        try:
            jax.block_until_ready(fn())
        finally:
            mon.unregister_event_duration_listener(on_duration)
        return len(seen)

    jitted = programs(lambda: tr.init_state(key, tokens))
    eager = programs(lambda: opt.init(lm.init(key, tokens)["params"]))
    assert 1 <= jitted <= 3 < eager, (jitted, eager)


def test_sync_jitted_init_lowers_as_before(topo8, monkeypatch):
    """The sync trainer's ``jit_init`` goes through
    ``common.placed_state``: the program it lowers is the one PR 27's
    inline ``jax.jit(create, out_shardings=replicated)`` lowered."""
    from mpit_tpu.parallel import common

    lm, tokens = _tiny_lm()
    opt, key = optax.adamw(1e-3), jax.random.key(2)
    tr = DataParallelTrainer(lm, opt, topo8, jit_init=True)
    texts, real_jit = [], jax.jit

    def spy(fun, **kw):
        jitted = real_jit(fun, **kw)

        def call(*args):
            texts.append(jitted.lower(*args).as_text())
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", spy)
    tr.init_state(key, tokens)
    monkeypatch.undo()
    before = real_jit(
        lambda key, x: common.TrainState.create(
            lm.init(key, x)["params"], opt
        ),
        out_shardings=topo8.replicated_sharding(),
    )
    assert texts == [before.lower(key, jnp.asarray(tokens)).as_text()]


def test_batches_shapes_and_determinism(mnist):
    x_tr, y_tr, *_ = mnist
    b = Batches(x_tr, y_tr, global_batch=128, seed=7)
    e0 = list(b.epoch(0))
    e0_again = list(b.epoch(0))
    assert len(e0) == b.steps_per_epoch() == len(x_tr) // 128
    np.testing.assert_array_equal(e0[0][0], e0_again[0][0])
    assert e0[0][0].shape == (128, 28, 28, 1)


def test_shard_for_worker_partitions():
    from mpit_tpu.data import shard_for_worker

    x = np.arange(100)
    shards = [shard_for_worker(x, w, 8) for w in range(8)]
    assert all(len(s) == 12 for s in shards)
    assert len(np.unique(np.concatenate(shards))) == 96
