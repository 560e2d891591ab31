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


def test_batches_shapes_and_determinism(mnist):
    x_tr, y_tr, *_ = mnist
    b = Batches(x_tr, y_tr, global_batch=128, seed=7)
    e0 = list(b.epoch(0))
    e0_again = list(b.epoch(0))
    assert len(e0) == b.steps_per_epoch() == len(x_tr) // 128
    np.testing.assert_array_equal(e0[0][0], e0_again[0][0])
    assert e0[0][0].shape == (128, 28, 28, 1)


class TestBucketedExchange:
    """ISSUE-11 bucketed / quantized gradient exchange: the staged
    bucket pipeline
    must reproduce the fused step, int8+EF must track it closely, and
    the armed path must journal honest roofline/dynamics records."""

    def _data(self, n=64, seed=3):
        rng = np.random.default_rng(seed)
        x = rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32)
        y = rng.integers(0, 10, n).astype(np.int32)
        return x, y

    def _run(self, topo, x, y, steps=3, **kw):
        model = LeNet(compute_dtype=jnp.float32)
        tr = DataParallelTrainer(
            model,
            optax.sgd(0.1, momentum=0.9),
            topo,
            donate_state=False,
            **kw,
        )
        st = tr.init_state(jax.random.key(0), x[:2])
        losses = []
        for _ in range(steps):
            st, m = tr.step(st, x, y)
            losses.append(float(m["loss"]))
        params = jax.tree.map(np.asarray, jax.device_get(st.params))
        return tr, losses, params

    def test_raw_bucketed_matches_fused(self, topo8):
        x, y = self._data()
        _, l_fused, p_fused = self._run(topo8, x, y)
        tr, l_b, p_b = self._run(
            topo8, x, y, quant="off", bucket_bytes=64 << 10
        )
        assert tr.bucketed and len(tr._plan.buckets) > 1
        np.testing.assert_allclose(l_b, l_fused, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5),
            p_b,
            p_fused,
        )

    def test_int8_ef_tracks_fused(self, topo8):
        x, y = self._data()
        _, l_fused, p_fused = self._run(topo8, x, y, steps=5)
        tr, l_q, p_q = self._run(
            topo8, x, y, steps=5, quant="int8", bucket_bytes=64 << 10
        )
        # error feedback keeps the quantized stream on the raw
        # trajectory: tight but not bit-equal
        assert all(np.isfinite(l_q))
        np.testing.assert_allclose(l_q, l_fused, atol=2e-2)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=5e-3),
            p_q,
            p_fused,
        )
        # int8 codes put ~4x fewer bytes on the wire than the raw
        # staged exchange over the same plan
        raw = DataParallelTrainer(
            LeNet(compute_dtype=jnp.float32),
            optax.sgd(0.1),
            topo8,
            donate_state=False,
            quant="off",
            bucket_bytes=64 << 10,
        )
        rs = raw.init_state(jax.random.key(0), x[:2])
        raw.step(rs, x, y)
        assert tr.wire_bytes_per_step() < raw.wire_bytes_per_step() / 3

    def test_obs_roofline_and_dynamics(self, topo8, tmp_path):
        from mpit_tpu.obs.core import ObsConfig
        from mpit_tpu.obs.dynamics import aggregate_dynamics
        from mpit_tpu.obs.merge import roofline

        x, y = self._data()
        steps = 4
        tr, losses, _ = self._run(
            topo8,
            x,
            y,
            steps=steps,
            quant="int8",
            bucket_bytes=64 << 10,
            obs=ObsConfig(dir=str(tmp_path)),
        )
        tr.close_obs()
        assert all(np.isfinite(losses))

        rr = roofline([str(tmp_path)])
        rank0 = rr["ranks"][0]
        assert rank0["role"] == "client"
        assert rank0["compute_s"] > 0 and rank0["wire_s"] > 0
        # every hop journals its exact byte count: 2 hops per bucket per
        # step, summing to the plan's per-step wire volume
        assert rank0["bytes"] == steps * tr.wire_bytes_per_step()
        assert rank0["sends"] == steps * 2 * len(tr._plan.buckets)

        rep = aggregate_dynamics([str(tmp_path)])
        assert rep["run"] is not None
        assert rep["run"]["clients"] == 1
        assert not rep["run"]["diverging"]
        c = rep["clients"][0]
        assert c["algo"] == "sync-dp" and c["rounds"] == steps
        assert c["elastic"]["final"] > 0  # EF residuals are live

    def test_env_knobs(self, topo8, monkeypatch):
        from mpit_tpu.parallel.sync import (
            dp_bucket_bytes_from_env,
            dp_quant_from_env,
        )

        assert dp_quant_from_env({}) == "off"
        assert dp_quant_from_env({"MPIT_DP_QUANT": "int8"}) == "int8"
        with pytest.raises(ValueError, match="MPIT_DP_QUANT"):
            dp_quant_from_env({"MPIT_DP_QUANT": "fp4"})
        assert dp_bucket_bytes_from_env({}) is None
        assert (
            dp_bucket_bytes_from_env({"MPIT_DP_BUCKET_BYTES": "4096"})
            == 4096
        )
        with pytest.raises(ValueError, match="MPIT_DP_BUCKET_BYTES"):
            dp_bucket_bytes_from_env({"MPIT_DP_BUCKET_BYTES": "0"})

        model = LeNet(compute_dtype=jnp.float32)
        monkeypatch.setenv("MPIT_DP_QUANT", "bf16")
        tr = DataParallelTrainer(model, optax.sgd(0.1), topo8)
        assert tr.bucketed and tr.quant == "bf16"
        monkeypatch.delenv("MPIT_DP_QUANT")
        # bucket bytes alone engages bucketing, unquantized
        monkeypatch.setenv("MPIT_DP_BUCKET_BYTES", "65536")
        tr = DataParallelTrainer(model, optax.sgd(0.1), topo8)
        assert tr.bucketed and tr.quant == "off"
        assert tr.bucket_bytes == 65536
        monkeypatch.delenv("MPIT_DP_BUCKET_BYTES")
        tr = DataParallelTrainer(model, optax.sgd(0.1), topo8)
        assert not tr.bucketed
        with pytest.raises(ValueError, match="quant"):
            DataParallelTrainer(model, optax.sgd(0.1), topo8, quant="q4")


def test_shard_for_worker_partitions():
    from mpit_tpu.data import shard_for_worker

    x = np.arange(100)
    shards = [shard_for_worker(x, w, 8) for w in range(8)]
    assert all(len(s) == 12 for s in shards)
    assert len(np.unique(np.concatenate(shards))) == 96
