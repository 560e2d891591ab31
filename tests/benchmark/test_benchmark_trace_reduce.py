"""The reduction from trace to metrics on planes built by hand, and the FLOP
count against a count by hand. CPU, seconds."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import trace_reduce  # noqa: E402


def hand_built_planes():
    """Two chips, two program runs each, times in ns (name, start, duration).

    Chip 0: busy 1000-5000 and 6000-10000 of the span 1000-10000. A 2000 ns
    ``%all-reduce`` at 2000-4000 has its first half under ``%fusion.1``. The
    second program is a ``%while`` around two fusions (500 ns of its own), and
    an asynchronous all-reduce (start to done, 9000-9800) lies wholly under
    ``%fusion.4``. Chip 1 idles 5000-6500 while the host is mostly inside a
    ``bench.input`` span."""
    return {
        "/device:TPU:0": {
            "Steps": [("0", 1000.0, 4000.0), ("1", 6000.0, 4000.0)],
            "XLA Modules": [("jit_round_step(1)", 1000.0, 4000.0),
                            ("jit_round_step(1)", 6000.0, 4000.0)],
            "XLA Ops": [
                ("%fusion.1 = f32[8] fusion(...)", 1000.0, 2000.0),
                ("%all-reduce.1 = f32[8] all-reduce(...)", 2000.0, 2000.0),
                ("%fusion.2 = f32[8] fusion(...)", 4000.0, 1000.0),
                ("%while.1 = (f32[8]) while(...)", 6000.0, 4000.0),
                ("%fusion.3 = f32[8] fusion(...)", 6000.0, 2000.0),
                ("%fusion.4 = f32[8] fusion(...)", 8500.0, 1500.0),
            ],
            "Async XLA Ops": [
                ("%all-reduce-start.2 = f32[8] all-reduce-start(...)", 9000.0, 800.0),
                ("%copy-start.1 = f32[8] copy-start(...)", 1200.0, 300.0),
            ],
            "TC Overlay": [],
        },
        "/device:TPU:1": {
            "XLA Modules": [("jit_round_step(1)", 1000.0, 4000.0),
                            ("jit_round_step(1)", 6500.0, 3500.0)],
            "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", 1000.0, 4000.0),
                        ("%fusion.3 = f32[8] fusion(...)", 6500.0, 3500.0)],
        },
        "/host:CPU": {
            "python3": [("bench.input", 4800.0, 1600.0),
                        ("bench.wait", 6400.0, 600.0)],
        },
    }


def test_busy_idle_collectives_and_gap_by_hand():
    out = trace_reduce.reduce_trace(hand_built_planes())
    assert out["devices"] == 2 and out["modules"] == 2
    # busy: chip 0 4000 + 4000, chip 1 4000 + 3500; both spans 9000
    assert out["busy_s"] == pytest.approx((8000 + 7500) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(9000 / 1e9)
    # the worst chip is chip 1: idle 1500 of 9000
    assert out["idle_share_worst"] == pytest.approx(1500 / 9000)
    # chip 0's collectives: 2000 + 800, of which 1000 + 800 hidden
    assert out["collective_s"] == pytest.approx(2800 / 1e9)
    assert out["collective_exposed_s"] == pytest.approx(1000 / 1e9)
    assert out["collective_exposed_share"] == pytest.approx(1000 / 9000)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["%while.1 = (f32[8]) while(...)"] == pytest.approx(500 / 1e9)
    assert ops["%fusion.1 = f32[8] fusion(...)"] == pytest.approx(2000 / 1e9)
    assert out["breakdown"]["device_ops"][-1][0].startswith("%while.1")
    assert out["breakdown"]["idle_gaps"] == [["bench.input", pytest.approx(1500 / 1e9)]]


def test_a_gap_no_span_covers_is_named_so():
    assert trace_reduce.name_gap((10.0, 20.0), [("bench.input", 30.0, 5.0)]) \
        == trace_reduce.NO_SPAN


def test_no_device_plane_gives_nothing_to_read():
    assert trace_reduce.reduce_trace({"/host:CPU": {"python3": []}}) == {}


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [[1, 4], [5, 7]]
    assert trace_reduce.overlap([[1, 4], [5, 7]], [[3, 6]]) == 2
    assert trace_reduce.clip([(0, 10)], 2, 5) == [(2, 5)]


def test_flops_agree_with_a_count_by_hand_for_the_rehearsal_transformer():
    """A token: 6 x the weights that enter a matmul (the tied head among
    them) + 12 L T d for full attention; a sample is T tokens."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import flops
    from mpit_tpu.models import get_model
    from mpit_tpu.parallel.common import default_loss_fn

    layers, d, heads, t, vocab, batch = 2, 64, 4, 32, 257, 4
    model = get_model("transformer", vocab_size=vocab, num_layers=layers,
                      d_model=d, num_heads=heads, d_ff=0, max_len=t)
    tokens = jax.ShapeDtypeStruct((batch, t), jnp.int32)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, t), jnp.int32))["params"],
        jax.random.key(0))
    counted = flops.train_flops_per_sample(
        default_loss_fn(model.apply), params, tokens, tokens)
    weights = layers * (4 * d * d + 2 * d * 4 * d) + vocab * d
    by_hand = t * (6 * weights + 12 * layers * t * d)
    assert counted == pytest.approx(by_hand, rel=0.02)
