"""The held experts' grouped products as Pallas kernels (``ops/grouped``),
interpreted on the CPU, against ``lax.ragged_dot`` and a dense product a
group: forward, the rows' cotangent and the weights' cotangent, the zeros
past the last group, the tile chooser, the selection rule and the
``rows_tiled`` counter on both of ``moe_ffn_held``'s paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops import grouped, moe

M, K, N = 384, 256, 256
#: the outputs in one tile of their width, and in two
TILES = {"one_output_tile": ((128, 256, 256), (128, 256, 256),
                             (128, 256, 256)),
         "two_output_tiles": ((128, 256, 128), (128, 256, 128),
                              (128, 128, 128))}
#: group sizes over a buffer of M rows in tiles of 128
LAYOUTS = {
    "empty_groups": [0, 100, 0, 0, 150, 0],
    "inside_and_across_tiles": [40, 60, 200, 30],  # ends 40, 100 in tile 0
    "mid_buffer_end": [128, 17, 0, 90],  # 235 of 384: NaN past it
    "every_row": [128, 128, 64, 64],
    "no_row": [0, 0, 0],
    "one_group_past_a_tile": [0, 300],
}


def _operands(sizes, seed=0):
    """Rows, weights and an output cotangent; the rows and the cotangent
    past the last group are NaN, as a buffer's unwritten tail may be."""
    ks = jax.random.split(jax.random.key(seed), 3)
    live = jnp.arange(M)[:, None] < sum(sizes)
    lhs = jnp.where(live, jax.random.normal(ks[0], (M, K)), jnp.nan)
    rhs = jax.random.normal(ks[1], (len(sizes), K, N)) / K ** 0.5
    ct = jnp.where(live, jax.random.normal(ks[2], (M, N)), jnp.nan)
    bf = lambda a: a.astype(jnp.bfloat16)
    return bf(lhs), bf(rhs), bf(ct), live


def _dense(lhs, rhs, sizes):
    """Row ``i`` of group ``g`` times ``rhs[g]`` in float64, rounded once
    to bfloat16; 0 past the last group."""
    group = np.repeat(np.arange(len(sizes)), sizes)
    out = np.zeros((M, rhs.shape[2]))
    x, w = np.asarray(lhs, np.float64), np.asarray(rhs, np.float64)
    for i, g in enumerate(group):
        out[i] = x[i] @ w[g]
    return jnp.asarray(out.astype(np.float32)).astype(jnp.bfloat16)


def _float32(lhs, rhs, ct, sizes, live):
    """The product and both cotangents in float32 at full precision, each
    rounded once to bfloat16."""
    f32 = lambda a: jnp.where(live, a, 0).astype(jnp.float32)
    out, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, precision=jax.lax.Precision.HIGHEST), f32(lhs),
        rhs.astype(jnp.float32))
    bf = lambda a: a.astype(jnp.bfloat16)
    return (bf(out), *map(bf, vjp(f32(ct))))


def _ragged(lhs, rhs, sizes, live):
    """``ragged_dot`` as ``moe._expert_rows`` runs it: every operand and
    result cut to the groups' rows."""
    cut = lambda a: jnp.where(live, a, 0)
    return cut(jax.lax.ragged_dot(cut(lhs), rhs, sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(lhs.dtype))


def _ulp(x):
    """One bfloat16 ulp at ``x`` (the smallest normal's under it)."""
    x = np.abs(np.asarray(x, np.float32))
    return 2.0 ** (np.floor(np.log2(np.maximum(x, 2.0 ** -126))) - 7)


def _f32(a):
    return np.asarray(a, np.float32)


def _within_one_ulp(got, want):
    """``got`` within one bfloat16 ulp of ``want``, or of the float32 sum's
    own rounding where a row's terms cancel (2^-20: 256 terms of order 1)."""
    diff = np.abs(_f32(got) - _f32(want))
    room = _ulp(want) + 2.0 ** -20
    assert (diff <= room).all(), float(np.max(diff / room))


@pytest.mark.parametrize("tiles", TILES.values(), ids=TILES.keys())
@pytest.mark.parametrize("layout", LAYOUTS.values(), ids=LAYOUTS.keys())
def test_forward_and_both_cotangents(layout, tiles):
    sizes = jnp.asarray(layout, jnp.int32)
    lhs, rhs, ct, live = _operands(layout)
    out, vjp = jax.vjp(lambda a, b: grouped.gmm(a, b, sizes, tiles), lhs, rhs)
    d_lhs, d_rhs = vjp(ct)
    rows = int(sum(layout))
    for got in (out, d_lhs, d_rhs):
        assert got.dtype == jnp.bfloat16 and np.isfinite(_f32(got)).all()
    # past the last group: exactly 0, forward and backward, NaN tail or not
    assert not _f32(out)[rows:].any() and not _f32(d_lhs)[rows:].any()
    # ragged_dot's float32 result cast once, as moe._expert_rows takes it
    _within_one_ulp(out, _ragged(lhs, rhs, sizes, live))
    # and each of the three against float32 at full precision, rounded once
    for got, want in zip((out, d_lhs, d_rhs),
                         _float32(lhs, rhs, ct, sizes, live)):
        _within_one_ulp(got, want)
    # a group without rows gets a zero weight gradient
    for g, size in enumerate(layout):
        if not size:
            assert not _f32(d_rhs[g]).any()


@pytest.mark.parametrize("layout", LAYOUTS.values(), ids=LAYOUTS.keys())
def test_forward_is_the_dense_product_a_group(layout):
    """Against a product a row in float64, rounded once: within one
    bfloat16 ulp (the kernel sums in float32, in tiles)."""
    sizes = jnp.asarray(layout, jnp.int32)
    lhs, rhs, _, _ = _operands(layout, seed=1)
    got = grouped.gmm(lhs, rhs, sizes, TILES["one_output_tile"])
    want = _dense(lhs, rhs, layout)
    _within_one_ulp(got, want)


def test_tiles_that_do_not_tile_are_refused():
    lhs, rhs, _, _ = _operands([100, 100])
    with pytest.raises(ValueError, match="do not tile"):
        grouped.gmm(lhs[:300], rhs, jnp.asarray([100, 100], jnp.int32),
                    TILES["one_output_tile"])


#: the expert cells' grouped products: uniform rows an expert held, hidden
#: size, (padded) expert width, the chunk's rows, and the row tile chosen
CELLS = {
    "laguna": (320, 3072, 1024, 5120, 128),
    "nemotron": (384, 2688, 2048, 6144, 256),
    "zaya": (512, 2048, 2048, 8192, 256),
}


@pytest.mark.parametrize("rows_per_group,d,width,rows,tm", CELLS.values(),
                         ids=CELLS.keys())
def test_tiles_come_from_the_shape(rows_per_group, d, width, rows, tm):
    """One rule for all three: the row tile from the group's rows, the
    row products' contraction whole (a group's weights stay in VMEM), every
    tile dividing its width and under the VMEM budget."""
    fwd, rows_ct, weights_ct = grouped.choose_tiles(rows_per_group, d, width,
                                                    jnp.bfloat16)
    assert fwd[0] == rows_ct[0] == weights_ct[0] == tm and rows % tm == 0
    assert fwd[1] == d and rows_ct[1] == width  # the whole contraction
    for (t_m, tk, tn), (k, n), kernel in (
            (fwd, (d, width), "gmm"), (rows_ct, (width, d), "gmm"),
            (weights_ct, (d, width), "tgmm")):
        assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
        assert grouped.vmem_estimate(kernel, t_m, tk, tn, 2) \
            <= grouped._VMEM_BUDGET
    assert grouped.takes(True, rows, d, width, rows_per_group)


@pytest.mark.parametrize("on_tpu,rows,k,n,expected", [
    (True, 8192, 2048, 2048, True),  # the Zaya cell's buffer on the chip
    (False, 8192, 2048, 2048, False),  # the same on the CPU
    (True, 64, 16, 8, False),  # the tests' widths of 16 and 8
    (True, 8192, 2048, 1856, False),  # a width no whole number of 128s
    (True, 8000, 2048, 2048, False),  # a buffer no whole number of tiles
    (True, 8192, 65536, 2048, False),  # a contraction VMEM cannot hold
], ids=["tpu_tiles", "cpu", "test_widths", "width_1856", "rows_8000",
        "contraction_65536"])
def test_the_kernel_is_taken_where_the_shape_tiles_on_a_tpu(
        on_tpu, rows, k, n, expected):
    assert grouped.takes(on_tpu, rows, k, n, 512) is expected


@pytest.mark.parametrize("layout,tm,expected", [
    ([40, 60, 200, 30], 128, 6 * 128),  # tile 0 three times, 2 twice
    ([0, 128, 0, 128], 128, 2 * 128),
    ([0, 0], 128, 0),
    ([300], 256, 2 * 256),
])
def test_rows_tiled_counts_a_shared_tile_once_a_group(layout, tm, expected):
    assert int(grouped.rows_tiled(jnp.asarray(layout), tm)) == expected


def _layer(monkeypatch, kernel, chunk):
    """One SwiGLU expert layer at widths that tile (128), 256 tokens,
    top-2 of 8, 4 held, its output, counters and gradients by every
    parameter and ``y``, through the kernels (interpreted) or
    ``ragged_dot``, in one pass or in chunks of ``chunk`` rows."""
    monkeypatch.setattr(grouped, "pallas_supported", lambda: kernel)
    monkeypatch.setattr(moe, "chunk_rows", lambda *a: chunk)
    ks = jax.random.split(jax.random.key(3), 5)
    d, width, held = 128, 128, 4
    init = lambda k, *s: jax.random.normal(k, s) / np.sqrt(s[-2])
    params = {"router": init(ks[0], d, 8).at[-1, :held].add(0.3),
              "w_gate": init(ks[1], held, d, width),
              "w_up": init(ks[2], held, d, width),
              "w_down": init(ks[3], held, width, d)}
    y = jax.random.normal(ks[4], (256, d)).at[:, -1].set(1.0)
    ct = jnp.cos(jnp.arange(256 * d, dtype=jnp.float32).reshape(256, d))

    def loss(params, y):
        out, counters, _ = moe.moe_ffn_held(
            params, y.astype(jnp.bfloat16), top_k=2, row_bound=512)
        return jnp.sum(out.astype(jnp.float32) * ct), (out, counters)

    (_, (out, counters)), grads = jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1), has_aux=True)(params, y)
    return out, counters, grads


@pytest.mark.parametrize("chunk", [512, 256], ids=["one_pass", "walk"])
def test_the_layer_through_the_kernels_is_the_ragged_layer(monkeypatch,
                                                           chunk):
    """``moe_ffn_held`` with the kernels forced (interpreted) against the
    same layer through ``ragged_dot``: the same output and gradients to
    bfloat16's rounding, the same counters but ``rows_tiled``, which the
    ragged path gives as ``rows_walked`` and the kernels as the row tiles
    visited times the tile."""
    got = _layer(monkeypatch, True, chunk)
    want = _layer(monkeypatch, False, chunk)
    for name in ("rows_held", "rows_walked", "rows_dropped",
                 "load_max_over_mean", "balance"):
        assert float(got[1][name]) == float(want[1][name]), name
    assert float(want[1]["rows_tiled"]) == float(want[1]["rows_walked"])
    held = float(got[1]["rows_held"])
    assert held <= float(got[1]["rows_tiled"]) <= held + 2 * 4 * 128
    assert float(got[1]["rows_tiled"]) % 128 == 0
    np.testing.assert_allclose(_f32(got[0]), _f32(want[0]),
                               rtol=2e-2, atol=2e-2)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, g), (_, w) in zip(flat(got[2]), flat(want[2])):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-2 * scale, \
            jax.tree_util.keystr(path)
