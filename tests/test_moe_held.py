"""The held-experts layer's chunk walk (``ops/moe._walk``) against its single
pass: the same output, counters and gradients wherever the step's count
falls among the chunks, and one copy of the layer's body in the program."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops import moe

TOKENS, D, WIDTH, ROUTED, HELD, TOP_K, CHUNK = 64, 16, 8, 8, 4, 2, 32


#: the two families of expert layer: SwiGLU experts behind softmax scores,
#: and ``relu2`` experts behind sigmoid scores with a bias on the choice
FAMILIES = ["swiglu", "relu2"]


def _layer(chosen_by: int, seed: int = 0, expert: str = "swiglu"):
    """Parameters and ``y`` whose first ``chosen_by`` tokens send both of
    their choices to the experts held and whose other tokens send none:
    ``2 * chosen_by`` rows (the last feature is a per-token bias's)."""
    ks = jax.random.split(jax.random.key(seed), 7)
    init = lambda k, *s: jax.random.normal(k, s) / np.sqrt(s[-2])
    params = {"router": init(ks[0], D, ROUTED).at[-1, :HELD].set(50.0),
              "w_gate": init(ks[1], HELD, D, WIDTH),
              "w_up": init(ks[2], HELD, D, WIDTH),
              "w_down": init(ks[3], HELD, WIDTH, D)}
    if expert == "relu2":  # no gate; sigmoid scores, a bias on the choice
        del params["w_gate"]
        params["bias"] = 1e-3 * jax.random.normal(ks[6], (ROUTED,))
        # a sigmoid at 50 is 1 to the last bit and passes no gradient
        params["router"] = params["router"].at[-1, :HELD].set(8.0)
    bias = jnp.where(jnp.arange(TOKENS) < chosen_by, 1.0, -1.0)
    y = jax.random.normal(ks[4], (TOKENS, D)).at[:, -1].set(bias)
    return params, y, jax.random.normal(ks[5], (TOKENS, D))


def _run(monkeypatch, chunk, params, y, ct, row_bound, expert="swiglu"):
    """Output, counters and the gradients of ``sum(out * ct)`` by ``y`` and
    every parameter (the router's passes through the routing weights),
    under ``jax.checkpoint`` as the block's remat runs the layer."""
    monkeypatch.setattr(moe, "chunk_rows", lambda *a: chunk)

    def loss(params, y):
        out, counters, _ = moe.moe_ffn_held(
            params, y, top_k=TOP_K, row_bound=row_bound, scale=2.5,
            expert=expert)
        return jnp.sum(out * ct), (out, counters)

    (_, (out, counters)), grads = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1), has_aux=True))(params, y)
    return out, counters, grads


@pytest.mark.parametrize("chosen_by,row_bound", [
    (0, 128),  # no row at all: no chunk is walked
    (8, 128),  # 16 rows: under one chunk
    (16, 128),  # 32 rows: exactly on the first chunk's edge
    (24, 128),  # 48 rows: an expert's group straddles the edge
    (33, 128),  # 66 rows: two rows into the third chunk
    (64, 128),  # 128 rows: every chunk full
    (64, 96),  # 128 rows against a bound of 96: 32 dropped and counted
    (40, 72),  # a bound that is no whole number of chunks, 8 rows past it
], ids=["zero", "under_one", "on_the_edge", "straddling", "into_the_third",
        "all_full", "past_the_bound", "ragged_bound"])
@pytest.mark.parametrize("expert", FAMILIES)
def test_the_walk_is_the_single_pass(monkeypatch, chosen_by, row_bound,
                                     expert):
    params, y, ct = _layer(chosen_by, expert=expert)
    want = _run(monkeypatch, 10 ** 6, params, y, ct, row_bound, expert)
    got = _run(monkeypatch, CHUNK, params, y, ct, row_bound, expert)
    count = min(2 * chosen_by, row_bound)
    assert float(got[1]["rows_held"]) == 2 * chosen_by
    assert float(got[1]["rows_dropped"]) == 2 * chosen_by - count
    assert float(got[1]["rows_walked"]) == math.ceil(count / CHUNK) * CHUNK
    assert float(want[1]["rows_walked"]) == row_bound
    for name in ("rows_held", "rows_dropped", "load_max_over_mean", "balance"):
        assert float(got[1][name]) == float(want[1][name])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    flat = lambda grads: jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), (_, w) in zip(flat(got[2]), flat(want[2])):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
    if chosen_by:  # the routing weights carry a gradient into the router
        assert float(jnp.abs(got[2][0]["router"]).max()) > 0


def test_a_group_straddles_the_edge_in_the_straddling_case():
    """What the case above is for: no expert's rows end on row 32."""
    params, y, _ = _layer(24)
    _, experts, _ = moe.route_top_k(y, params["router"], TOP_K)
    ends = np.cumsum(np.bincount(np.asarray(experts).reshape(-1),
                                 minlength=ROUTED)[:HELD])
    assert ends[-1] == 48 and CHUNK not in ends


@pytest.mark.parametrize("expert", FAMILIES)
def test_constant_routing_weights_take_no_gradient(monkeypatch, expert):
    """``routing_grad=False`` under the walk: the router's only gradient
    would come through the weights, so it is zero, and ``y``'s is the
    single pass's."""
    params, y, ct = _layer(40, expert=expert)

    def grads(chunk):
        monkeypatch.setattr(moe, "chunk_rows", lambda *a: chunk)
        return jax.grad(lambda p, y: jnp.sum(ct * moe.moe_ffn_held(
            p, y, top_k=TOP_K, row_bound=128, routing_grad=False,
            expert=expert)[0]),
            argnums=(0, 1))(params, y)

    got, want = grads(CHUNK), grads(10 ** 6)
    assert float(jnp.abs(got[0]["router"]).max()) == 0
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-6)


def test_the_chunk_comes_from_the_shape():
    # the Laguna cell: 8,192 tokens, top-10 of 256, 8 held -> 2 x 2,560
    assert moe.chunk_rows(8192, 10, 8, 256) == 5120
    assert moe.chunk_rows(64, 3, 4, 16) == 512  # whole multiples of 256


def test_the_program_holds_the_layers_body_once():
    """The guard a switch over buffer sizes would have tripped (PR 30: four
    copies of the layer, 4.5 times the text, nine seconds of set-up): four
    remat'd layers under ``value_and_grad`` lower to no more than 1.25
    times the single pass's text, whatever the number of chunks. (On the
    CPU ``ragged_dot`` lowers to plain operations, so lines are counted.)"""
    tokens, top_k, routed, held = 512, 10, 256, 8
    ks = jax.random.split(jax.random.key(0), 5)
    init = lambda k, *s: jax.random.normal(k, s) / np.sqrt(s[-2])
    layers = [{"router": init(ks[0], D, routed),
               "w_gate": init(ks[1], held, D, WIDTH),
               "w_up": init(ks[2], held, D, WIDTH),
               "w_down": init(ks[3], held, WIDTH, D)}] * 4
    y = jax.random.normal(ks[4], (tokens, D))
    chunk = moe.chunk_rows(tokens, top_k, held, routed)

    def lines(row_bound):
        def layer(p, x):
            return x + moe.moe_ffn_held(
                p, x, top_k=top_k, row_bound=row_bound)[0]

        def loss(layers, x):
            for p in layers:
                x = jax.checkpoint(layer)(p, x)
            return jnp.sum(x ** 2)
        text = jax.jit(jax.value_and_grad(loss)).lower(layers, y).as_text()
        return len(text.splitlines()), text.count("stablehlo.while")

    single, no_loop = lines(chunk)
    for chunks in (2, 8):
        walked, loops = lines(chunks * chunk)
        assert walked <= 1.25 * single, (chunks, walked, single)
        assert loops > no_loop  # and the walk is what was lowered


@pytest.mark.parametrize("expert", FAMILIES)
def test_a_width_padded_to_whole_tiles_is_the_same_layer(monkeypatch, expert):
    """The held experts' width is padded with zeros to a multiple of
    ``_WIDTH_TILE`` once it is over one tile (1,856 -> 2,048 in the Nemotron
    cell): the same output and the same gradients, in the parameters'
    own shapes."""
    params, y, ct = _layer(40, expert=expert)
    want = _run(monkeypatch, CHUNK, params, y, ct, 128, expert)
    monkeypatch.setattr(moe, "_WIDTH_TILE", 6)  # WIDTH 8 -> 12
    got = _run(monkeypatch, CHUNK, params, y, ct, 128, expert)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    flat = lambda grads: jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), (_, w) in zip(flat(got[2]), flat(want[2])):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
