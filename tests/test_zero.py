"""ZeRO-1 sharded-optimizer DP ≡ plain sync DP, with state truly sharded.

The chunked update is pure bookkeeping for elementwise optimizers: the
trajectory must match DataParallelTrainer exactly, while Adam's mu/nu
live 1/W per device instead of replicated.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import mpit_tpu
from mpit_tpu.models import LeNet
from mpit_tpu.parallel import DataParallelTrainer, ZeroDataParallelTrainer


def _data(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    return x, y


class TestZero:
    def test_matches_plain_dp_trajectory(self, topo8):
        """Adam through the chunked update equals replicated Adam."""
        model = LeNet(compute_dtype=jnp.float32)
        opt = optax.adam(1e-3)
        x, y = _data()
        results = {}
        for cls in (DataParallelTrainer, ZeroDataParallelTrainer):
            tr = cls(model, opt, topo8, donate_state=False)
            st = tr.init_state(jax.random.key(0), x[:2])
            losses = []
            for _ in range(3):
                st, m = tr.step(st, x, y)
                losses.append(float(m["loss"]))
            results[cls.__name__] = (
                losses,
                jax.tree.map(np.asarray, jax.device_get(st.params)),
                tr.evaluate(st, x, y),
            )
        a = results["DataParallelTrainer"]
        b = results["ZeroDataParallelTrainer"]
        np.testing.assert_allclose(b[0], a[0], rtol=1e-5)
        jax.tree.map(
            lambda p, q: np.testing.assert_allclose(p, q, atol=2e-5),
            b[1], a[1],
        )
        assert b[2][0] == pytest.approx(a[2][0], abs=1e-6)

    def test_optimizer_state_actually_sharded(self, topo8):
        """The point of ZeRO: Adam's mu/nu land P(worker-axis), 1/W per
        device, while params stay replicated."""
        model = LeNet(compute_dtype=jnp.float32)
        tr = ZeroDataParallelTrainer(
            model, optax.adam(1e-3), topo8, donate_state=False
        )
        x, y = _data()
        st = tr.init_state(jax.random.key(0), x[:2])
        axis = topo8.worker_axis
        flat_leaves = [
            a for a in jax.tree.leaves(st.opt_state)
            if getattr(a, "ndim", 0) == 1 and a.size >= 8
        ]
        assert flat_leaves, "no parameter-sized optimizer leaves found"
        for leaf in flat_leaves:
            assert leaf.sharding.spec[0] == axis, leaf.sharding
        # params replicated
        k = jax.tree.leaves(st.params)[0]
        assert all(s is None for s in (k.sharding.spec or [None]))
        # and the sharding survives a step
        st, _ = tr.step(st, x, y)
        mu = [
            a for a in jax.tree.leaves(st.opt_state)
            if getattr(a, "ndim", 0) == 1 and a.size >= 8
        ][0]
        assert mu.sharding.spec[0] == axis

    def test_composes_with_grad_accumulation(self, topo8):
        """Both memory knobs together: accumulated ZeRO equals plain DP
        on the same global batch."""
        model = LeNet(compute_dtype=jnp.float32)
        opt = optax.adam(1e-3)
        x, y = _data(n=32, seed=2)
        ref = DataParallelTrainer(model, opt, topo8, donate_state=False)
        st_r = ref.init_state(jax.random.key(0), x[:2])
        za = ZeroDataParallelTrainer(
            model, opt, topo8, donate_state=False, accum_steps=2
        )
        st_z = za.init_state(jax.random.key(0), x[:2])
        for _ in range(2):
            st_r, m_r = ref.step(st_r, x, y)
            st_z, m_z = za.step(st_z, x, y)
            np.testing.assert_allclose(
                float(m_z["loss"]), float(m_r["loss"]), rtol=1e-5
            )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5
            ),
            st_z.params, st_r.params,
        )
        with pytest.raises(ValueError, match="accum_steps"):
            za.step(st_z, x[:8], y[:8])  # per-worker 1 % 2 != 0

    def test_quantized_scatter_tracks_raw(self, topo8):
        """quant="int8" routes the reduce-scatter through the blockwise
        quantized codes (stateless — docs/WIRE.md); the trajectory must
        stay close to the raw scatter, and mode "off" must be it."""
        model = LeNet(compute_dtype=jnp.float32)
        opt = optax.sgd(0.1, momentum=0.9)
        x, y = _data(n=32, seed=4)
        results = {}
        for mode in ("off", "int8"):
            tr = ZeroDataParallelTrainer(
                model, opt, topo8, donate_state=False, quant=mode
            )
            assert tr.quant == mode
            st = tr.init_state(jax.random.key(0), x[:2])
            losses = []
            for _ in range(3):
                st, m = tr.step(st, x, y)
                losses.append(float(m["loss"]))
            results[mode] = (
                losses,
                jax.tree.map(np.asarray, jax.device_get(st.params)),
            )
        assert all(np.isfinite(results["int8"][0]))
        np.testing.assert_allclose(
            results["int8"][0], results["off"][0], atol=2e-2
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=5e-3),
            results["int8"][1], results["off"][1],
        )
        with pytest.raises(ValueError, match="quant"):
            ZeroDataParallelTrainer(
                model, optax.sgd(0.1), topo8, quant="fp4"
            )

    def test_quant_mode_from_the_environment(self, topo8, monkeypatch):
        """``MPIT_DP_QUANT`` has one reader and this trainer is its one
        user: default off, a known mode accepted, an unknown one named."""
        from mpit_tpu.parallel.zero import dp_quant_from_env

        assert dp_quant_from_env({}) == "off"
        assert dp_quant_from_env({"MPIT_DP_QUANT": "int8"}) == "int8"
        with pytest.raises(ValueError, match="MPIT_DP_QUANT"):
            dp_quant_from_env({"MPIT_DP_QUANT": "fp4"})
        monkeypatch.setenv("MPIT_DP_QUANT", "bf16")
        model = LeNet(compute_dtype=jnp.float32)
        assert ZeroDataParallelTrainer(
            model, optax.sgd(0.1), topo8).quant == "bf16"

    def test_cross_leaf_optimizer_rejected(self, topo8):
        """Global-norm clipping over a CHUNK would differ per device —
        the behavioral probe refuses it up front."""
        with pytest.raises(ValueError, match="ELEMENTWISE"):
            ZeroDataParallelTrainer(
                LeNet(),
                optax.chain(
                    optax.clip_by_global_norm(1.0), optax.sgd(0.1)
                ),
                topo8,
            )

    def test_fit_and_w_invariance(self):
        """fit() through the shared loop; W=8 equals W=1 on the same
        global batch (the psum_scatter mean is the full mean)."""
        from mpit_tpu.data import Batches

        model = LeNet(compute_dtype=jnp.float32)
        opt = optax.sgd(0.1, momentum=0.9)
        x, y = _data(n=32, seed=1)
        results = {}
        for w in (8, 1):
            mpit_tpu.finalize()
            topo = mpit_tpu.init(num_workers=w)
            tr = ZeroDataParallelTrainer(
                model, opt, topo, donate_state=False
            )
            st = tr.init_state(jax.random.key(0), x[:2])
            st, m = tr.fit(
                Batches(x, y, global_batch=16, seed=0), st, epochs=2
            )
            results[w] = (
                float(m["loss"]),
                jax.tree.map(np.asarray, jax.device_get(st.params)),
            )
            mpit_tpu.finalize()
        assert results[8][0] == pytest.approx(results[1][0], rel=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=3e-5),
            results[8][1], results[1][1],
        )


@pytest.mark.slow
class TestClipNorm:
    def test_clip_matches_optax_chain_on_plain_dp(self, topo8):
        """clip_norm through the chunked update == optax.clip_by_global_norm
        on plain sync DP (where the chain IS safe, since grads are
        pmean-ed before the update). Clipping must actually engage."""
        model = LeNet(compute_dtype=jnp.float32)
        x, y = _data()
        c = 0.05  # far below a fresh LeNet's CE gradient norm

        ref = DataParallelTrainer(
            model,
            optax.chain(optax.clip_by_global_norm(c), optax.sgd(0.1)),
            topo8, donate_state=False,
        )
        st_r = ref.init_state(jax.random.key(0), x[:2])
        # prove the threshold engages: the unclipped grad norm exceeds c
        g = jax.grad(
            lambda p: optax.softmax_cross_entropy_with_integer_labels(
                model.apply({"params": p}, jnp.asarray(x)), jnp.asarray(y)
            ).mean()
        )(st_r.params)
        assert float(optax.global_norm(g)) > c

        zt = ZeroDataParallelTrainer(
            model, optax.sgd(0.1), topo8, donate_state=False, clip_norm=c
        )
        st_z = zt.init_state(jax.random.key(0), x[:2])
        for _ in range(3):
            st_r, mr = ref.step(st_r, x, y)
            st_z, mz = zt.step(st_z, x, y)
            assert float(mz["loss"]) == pytest.approx(
                float(mr["loss"]), rel=1e-6
            )
        jax.tree.map(
            lambda p, q: np.testing.assert_allclose(
                np.asarray(p), np.asarray(q), atol=2e-6
            ),
            jax.device_get(st_z.params), jax.device_get(st_r.params),
        )

    def test_clip_validation(self, topo8):
        model = LeNet(compute_dtype=jnp.float32)
        with pytest.raises(ValueError, match="clip_norm"):
            ZeroDataParallelTrainer(
                model, optax.sgd(0.1), topo8, clip_norm=0.0
            )
