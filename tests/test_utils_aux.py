"""Aux-subsystem tests: checkpoint/resume, metrics, config, profiling.

The reference had none of these (SURVEY.md §5) — these tests pin down the
do-better behavior: checkpoints must reproduce the EASGD center variable
exactly, resume must continue (not restart) training, configs must
round-trip, presets must map to the five baseline configs.
"""

import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import mpit_tpu
from mpit_tpu.models import MLP
from mpit_tpu.parallel import DataParallelTrainer, EASGDTrainer
from mpit_tpu.utils import (
    PRESETS,
    MetricsLogger,
    TrainConfig,
    latest_checkpoint,
    list_checkpoints,
    restore_checkpoint,
    save_checkpoint,
)


def _tiny_batches(w=8, tau=2, b=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 1, (tau, w * b, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, (tau, w * b)).astype(np.int32)
    return x, y


class TestCheckpoint:
    def test_roundtrip_easgd_state_center_exact(self, topo8, tmp_path):
        """Resume must reproduce the center variable bit-exactly
        (SURVEY.md §5 checkpoint item)."""
        model = MLP(hidden=(16,), compute_dtype=jnp.float32)
        tr = EASGDTrainer(model, optax.sgd(0.1, momentum=0.9), topo8, tau=2)
        x, y = _tiny_batches()
        state = tr.init_state(jax.random.key(0), x[0, :2])
        state, _ = tr.step(state, x, y)

        save_checkpoint(str(tmp_path), state, step=int(state.round))
        template = tr.init_state(jax.random.key(1), x[0, :2])  # different rng
        restored, step = restore_checkpoint(str(tmp_path), template)
        assert step == 1

        for a, b in zip(
            jax.tree.leaves(jax.device_get(state.center)),
            jax.tree.leaves(jax.device_get(restored.center)),
        ):
            np.testing.assert_array_equal(a, b)
        # worker-sharded leaves too
        for a, b in zip(
            jax.tree.leaves(jax.device_get(state.worker_params)),
            jax.tree.leaves(jax.device_get(restored.worker_params)),
        ):
            np.testing.assert_array_equal(a, b)

    def test_resume_continues_training(self, topo8, tmp_path):
        """Train 2 rounds, checkpoint, train 2 more; vs restore + 2 rounds —
        identical final state (deterministic data ⇒ bit-equal)."""
        model = MLP(hidden=(16,), compute_dtype=jnp.float32)
        tr = EASGDTrainer(model, optax.sgd(0.1), topo8, tau=2,
                          donate_state=False)
        x1, y1 = _tiny_batches(seed=1)
        x2, y2 = _tiny_batches(seed=2)
        state = tr.init_state(jax.random.key(0), x1[0, :2])
        state, _ = tr.step(state, x1, y1)
        save_checkpoint(str(tmp_path), state, step=1)
        state, _ = tr.step(state, x2, y2)
        final_direct = jax.device_get(tr.center_params(state))

        template = tr.init_state(jax.random.key(9), x1[0, :2])
        shardings = jax.tree.map(lambda a: a.sharding, template)
        restored, step = restore_checkpoint(
            str(tmp_path), template, shardings=shardings
        )
        assert step == 1
        restored, _ = tr.step(restored, x2, y2)
        final_resumed = jax.device_get(tr.center_params(restored))
        for a, b in zip(
            jax.tree.leaves(final_direct), jax.tree.leaves(final_resumed)
        ):
            np.testing.assert_array_equal(a, b)

    def test_retention_and_latest(self, tmp_path):
        state = {"w": jnp.arange(4.0)}
        for s in (1, 2, 3, 4, 5):
            save_checkpoint(str(tmp_path), state, step=s, keep=3)
        assert list_checkpoints(str(tmp_path)) == [3, 4, 5]
        assert latest_checkpoint(str(tmp_path)) == 5

    def test_restore_empty_dir_cold_start(self, tmp_path):
        template = {"w": jnp.ones(3)}
        state, step = restore_checkpoint(str(tmp_path / "nope"), template)
        assert step is None
        np.testing.assert_array_equal(state["w"], np.ones(3))

    def test_specific_step_and_metadata(self, tmp_path):
        for s in (10, 20):
            save_checkpoint(
                str(tmp_path), {"w": jnp.full(2, float(s))}, step=s,
                metadata={"algo": "easgd"},
            )
        state, step = restore_checkpoint(
            str(tmp_path), {"w": jnp.zeros(2)}, step=10
        )
        assert step == 10
        np.testing.assert_array_equal(state["w"], np.full(2, 10.0))
        meta = json.load(open(tmp_path / "ckpt_00000010.json"))
        assert meta == {"step": 10, "algo": "easgd"}


class TestMetrics:
    def test_jsonl_records(self):
        buf = io.StringIO()
        log = MetricsLogger(tag="t", echo=False, _stream=buf)
        log.log(1, loss=jnp.float32(0.5), acc=0.9)
        log.log(2, loss=0.25)
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert [l["step"] for l in lines] == [1, 2]
        assert lines[0]["loss"] == 0.5 and lines[0]["tag"] == "t"
        assert lines[0]["process"] == 0

    def test_file_append_and_dirs(self, tmp_path):
        p = tmp_path / "sub" / "m.jsonl"
        with MetricsLogger(path=str(p), echo=False) as log:
            log.log(0, loss=1.0)
        with MetricsLogger(path=str(p), echo=False) as log:
            log.log(1, loss=0.5)
        lines = open(p).read().splitlines()
        assert len(lines) == 2

    def test_nonscalar_values_serialize(self):
        buf = io.StringIO()
        log = MetricsLogger(tag="t", echo=False, _stream=buf)
        log.log(0, grad_norms=np.arange(3.0), name="run", counts=[1, 2])
        rec = json.loads(buf.getvalue())
        assert rec["grad_norms"] == [0.0, 1.0, 2.0]
        assert rec["name"] == "run" and rec["counts"] == [1, 2]


class TestConfig:
    def test_presets_cover_baseline_configs(self):
        # BASELINE.md table rows 1-5 (+ the literal ps shape); extras must
        # be a superset, never displace a baseline config
        assert set(PRESETS) >= {
            "mnist-easgd", "mnist-ps", "cifar-vgg-sync",
            "alexnet-downpour", "resnet50-sync", "ptb-lstm-easgd",
        }
        assert "ptb-transformer-seq" in PRESETS  # beyond-parity preset

    def test_json_roundtrip(self):
        cfg = TrainConfig(model="vgg", lr=0.02, tau=8)
        cfg2 = TrainConfig.from_json(cfg.to_json())
        assert cfg2 == cfg

    def test_from_args_preset_overlay(self):
        cfg = TrainConfig.from_args(["--preset", "cifar-vgg-sync"])
        assert cfg.model == "vgg" and cfg.algo == "sync"
        assert cfg.dataset == "cifar10"

    def test_explicit_flag_beats_preset(self):
        cfg = TrainConfig.from_args(
            ["--preset", "cifar-vgg-sync", "--lr", "0.5"]
        )
        assert cfg.lr == 0.5 and cfg.model == "vgg"

    def test_explicit_default_valued_flag_beats_preset(self):
        # --lr 0.05 IS the dataclass default; typing it must still win over
        # the preset's lr (ptb preset sets lr=1.0)
        cfg = TrainConfig.from_args(
            ["--preset", "ptb-lstm-easgd", "--lr", "0.05"]
        )
        assert cfg.lr == 0.05 and cfg.model == "lstm"

    def test_unknown_preset_raises(self):
        with pytest.raises(ValueError, match="unknown preset"):
            TrainConfig().apply_preset("nope")


class TestProfiling:
    def test_trace_noop_without_dir(self):
        from mpit_tpu.utils.profiling import trace

        with trace(None):
            pass

    def test_trace_writes(self, tmp_path):
        from mpit_tpu.utils.profiling import trace

        with trace(str(tmp_path)):
            jax.block_until_ready(jnp.ones(8) * 2)
        assert os.listdir(tmp_path)  # trace artifacts exist


def test_state_to_host_sharded_leaf(topo8):
    """state_to_host is the collective-safe gather save_checkpoint routes
    every leaf through; on a fully-addressable mesh it must be a plain
    value-preserving fetch for sharded and replicated leaves alike."""
    from mpit_tpu.utils.checkpoint import state_to_host

    val = np.arange(16, dtype=np.float32).reshape(8, 2)
    sharded = jax.device_put(val, topo8.worker_sharding())
    replicated = jax.device_put(val, topo8.replicated_sharding())
    host = state_to_host({"s": sharded, "r": replicated, "n": 3})
    np.testing.assert_array_equal(host["s"], val)
    np.testing.assert_array_equal(host["r"], val)
    assert host["n"] == 3


class TestForceCompletion:
    """The shared completion-proof helper (the block_until_ready-lies
    workaround): must fetch one scalar per argument and survive pytrees
    with non-floating leaves (ints, PRNG keys — review-caught crash)."""

    def test_returns_data_dependent_scalar_per_argument(self):
        import jax.numpy as jnp

        from mpit_tpu.utils import force_completion

        state = {"w": jnp.full((4, 3), 2.0), "step": jnp.int32(7)}
        metrics = {"loss": jnp.float32(1.5)}
        # smallest floating leaf of each arg: w (sum 24.0) + loss (1.5)
        assert force_completion(state, metrics) == 25.5

    def test_prng_key_and_int_leaves_are_skipped(self):
        import jax
        import jax.numpy as jnp

        from mpit_tpu.utils import force_completion

        tree = {
            "key": jax.random.key(0),
            "count": jnp.int32(3),
            "p": jnp.ones(5),
        }
        assert force_completion(tree) == 5.0

    def test_no_floating_leaves_falls_back(self):
        import jax.numpy as jnp

        from mpit_tpu.utils import force_completion

        assert force_completion({"i": jnp.int32(1)}) == 0.0
