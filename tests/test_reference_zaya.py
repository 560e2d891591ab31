"""The ``zaya`` family (``layer_types`` of ``hybrid``: compressed
convolutional attention, then top-k experts routed by an MLP with depth
averaging) against its plain reference, ``models/reference_zaya.py``, on
seeded weights at small sizes: logits, loss and every gradient leaf, plain
and remat'd; causality of the convolutions and the value shift; each of
CCA's steps against its formula; the router's state carried from layer to
layer; one chip's share of the experts against the uncut layer. CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import arch as arch_mod
from mpit_tpu.models import reference_zaya as ref
from mpit_tpu.models.transformer import Block, TransformerLM
from mpit_tpu.ops import cca as cca_ops
from mpit_tpu.ops import moe

V, T = 97, 16
ARCH = {
    "model_type": "zaya", "hidden_size": 32, "num_hidden_layers": 2,
    "layer_types": ["hybrid", "hybrid"], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "cca_time0": 2, "cca_time1": 2,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
    "router_hidden_size": 16, "num_experts": 8, "num_experts_per_tok": 1,
    "moe_intermediate_size": 24, "tie_word_embeddings": True,
    "hidden_act": "silu", "attention_bias": False, "lm_head_bias": False,
    "sliding_window": None, "max_position_embeddings": 131072,
    "embedding_multiplier": 4.0,
    "vocab_size": V, "moe_routing_no_grad": True,
    "router_aux_loss_coef": 0.001,
}


def model(arch=ARCH, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    return TransformerLM(vocab_size=V, arch=arch, **kw)


def tokens(seed=0, t=T):
    return jax.random.randint(jax.random.key(seed), (2, t), 0, V)


@pytest.fixture(scope="module")
def params():
    return jax.jit(model().init)(jax.random.key(1), tokens())["params"]


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def ref_loss_and_grad(params, x, y, **kw):
    return jax.jit(lambda p, a, b: ref.loss_and_grad(p, a, b, ARCH, **kw))(
        params, x, y)


@pytest.fixture(scope="module")
def reference(params):
    """The reference's logits, loss and gradient on one batch."""
    x, y = tokens(2), tokens(3)
    logits = jax.jit(lambda p, a: ref.logits(p, a, ARCH))(params, x)
    return (x, y, logits, *ref_loss_and_grad(params, x, y))


def system_loss_and_grad(m, params, x, y):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: m.loss_with_counters(p, x, y)[0]))(params)


# -- the model against the reference --------------------------------------------

@pytest.mark.parametrize("remat,attn_impl", [
    (False, "xla"), (True, "xla"), (True, "flash_force")])
def test_loss_logits_and_every_gradient_leaf_agree(params, reference, remat,
                                                   attn_impl):
    m = model(remat=remat, attn_impl=attn_impl)
    x, y, ref_logits, ref_loss, ref_grads = reference
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: m.apply({"params": p}, x))(params)
    close(logits, ref_logits, 1e-4)
    loss, grads = system_loss_and_grad(m, params, x, y)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    for (path, r), g in zip(flat, jax.tree.leaves(grads)):
        err = float(jnp.linalg.norm(g - r) / (jnp.linalg.norm(r) + 1e-30))
        assert err < 1e-3, (jax.tree_util.keystr(path), err)


def test_the_share_holds_experts_and_no_router_leaf(params):
    block = params["Block_0"]
    assert "moe_router" not in block
    assert block["router_down"].shape == (32, 16)
    assert block["router_w3"].shape == (16, 8)
    assert block["cca_conv0"].shape == (6 * 16, 2)
    assert block["cca_conv1"].shape == (6, 16, 16, 2)
    assert block["cca_v_now"].shape == block["cca_v_back"].shape == (32, 16)
    assert block["router_eda"].shape == () and float(block["router_eda"]) == 0.5
    assert "head" not in params  # tied


def test_the_new_scopes_are_registered_and_in_the_program(params):
    from mpit_tpu.utils import profiling

    text = jax.jit(lambda p, x: model().apply({"params": p}, x)).lower(
        params, tokens()).as_text(debug_info=True)
    for scope in ("cca_mix", "router_mlp"):
        assert scope in profiling.scopes() and scope in text, scope


def test_by_layer_is_the_whole_and_given_choices_are_read(params, reference):
    x, y, _, loss, grads = reference
    by_loss, by_grads, own = ref.loss_and_grad_by_layer(params, x, y, ARCH)
    assert float(by_loss) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(by_grads), jax.tree.leaves(grads)):
        close(a, b, 1e-5)
    assert [o.shape for o in own] == [(2, T, 1)] * 2
    # other choices given are read
    shifted = [(o + 1) % 8 for o in own]
    other, _, _ = ref.loss_and_grad_by_layer(params, x, y, ARCH,
                                             choices=shifted)
    assert abs(float(other) - float(by_loss)) > 1e-4


def test_a_lower_precision_moves_the_reference(params, reference):
    x, y, _, _, grads = reference
    _, low = ref_loss_and_grad(params, x, y, operand_dtype=jnp.float8_e4m3fn)
    err = float(jnp.linalg.norm(low["Block_0"]["cca_q"] - grads["Block_0"][
        "cca_q"]) / jnp.linalg.norm(grads["Block_0"]["cca_q"]))
    assert err > 0.2


# -- causality -----------------------------------------------------------------

def test_a_later_token_moves_nothing_before_it(params):
    """A change at token t + 1 reaches no position <= t: through both
    convolutions and the value shift, in the system and the reference."""
    m = model()
    x = tokens(8)
    t = 9
    later = x.at[:, t + 1].set((x[:, t + 1] + 5) % V)
    run = jax.jit(lambda p, s: m.apply({"params": p}, s))
    plain = jax.jit(lambda p, s: ref.logits(p, s, ARCH))
    for f in (lambda s: run(params, s), lambda s: plain(params, s)):
        a, b = f(x), f(later)
        np.testing.assert_array_equal(np.asarray(a[:, :t + 1]),
                                      np.asarray(b[:, :t + 1]))
        assert float(jnp.abs(a[:, t + 1:] - b[:, t + 1:]).max()) > 1e-4


def test_each_op_step_is_causal():
    key = jax.random.split(jax.random.key(9), 6)
    u = jax.random.normal(key[0], (1, 12, 6, 16))
    taps0 = jax.random.normal(key[1], (6 * 16, 2))
    taps1 = jax.random.normal(key[2], (6, 16, 16, 2))
    later = u.at[:, 7].add(1.0)
    a, b = (cca_ops.conv_mix(v, taps0, taps1) for v in (u, later))
    np.testing.assert_array_equal(np.asarray(a[:, :7]), np.asarray(b[:, :7]))
    assert float(jnp.abs(a[:, 7:9] - b[:, 7:9]).min()) > 0  # two taps each
    np.testing.assert_array_equal(np.asarray(a[:, 10:]), np.asarray(b[:, 10:]))
    now, back = (jax.random.normal(k, (1, 12, 16)) for k in key[3:5])
    v = cca_ops.value_shift(now, back.at[:, 5].add(1.0), 2)
    w = cca_ops.value_shift(now, back, 2)
    changed = np.nonzero(np.abs(np.asarray(v - w)).sum((0, 2, 3)))[0]
    assert list(changed) == [6]  # token 5's second halves, read by token 6


# -- each step against its formula ----------------------------------------------

def test_value_shift_is_its_formula():
    now = jnp.arange(2 * 4 * 8, dtype=jnp.float32).reshape(2, 4, 8)
    back = -now
    v = np.asarray(cca_ops.value_shift(now, back, 2))
    assert v.shape == (2, 4, 2, 8)
    for t in range(4):
        for g in range(2):
            np.testing.assert_array_equal(v[:, t, g, :4], now[:, t, 4 * g:4 * g + 4])
            want = back[:, t - 1, 4 * g:4 * g + 4] if t else np.zeros((2, 4))
            np.testing.assert_array_equal(v[:, t, g, 4:], want)


def test_the_two_convolutions_are_their_formula():
    key = jax.random.split(jax.random.key(10), 3)
    h, d, t = 3, 4, 5
    u = np.asarray(jax.random.normal(key[0], (1, t, h, d)), np.float64)
    a = np.asarray(jax.random.normal(key[1], (h * d, 2)), np.float64)
    b = np.asarray(jax.random.normal(key[2], (h, d, d, 2)), np.float64)
    at = lambda arr, s: arr[:, s] if s >= 0 else np.zeros_like(arr[:, 0])
    taps = a.reshape(h, d, 2)
    c = np.stack([sum(taps[..., j] * at(u, s - j) for j in range(2))
                  for s in range(t)], axis=1)
    z = np.stack([sum(np.einsum("bhd,hde->bhe", at(c, s - j), b[..., j])
                      for j in range(2)) for s in range(t)], axis=1)
    with jax.default_matmul_precision("highest"):
        got = cca_ops.conv_mix(jnp.asarray(u, jnp.float32),
                               jnp.asarray(a, jnp.float32),
                               jnp.asarray(b, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), z, rtol=1e-5, atol=1e-5)


def test_the_qk_mean_and_the_norms_are_their_formula():
    key = jax.random.split(jax.random.key(11), 5)
    q0 = jax.random.normal(key[0], (1, 3, 4, 8))
    k0 = jax.random.normal(key[1], (1, 3, 2, 8))
    zq = jax.random.normal(key[2], (1, 3, 4, 8))
    zk = jax.random.normal(key[3], (1, 3, 2, 8))
    q, k = cca_ops.qk_mean(zq, zk, q0, k0)
    for i in range(4):  # query heads 0, 1 on key head 0; 2, 3 on 1
        close(q[:, :, i], zq[:, :, i] + (q0[:, :, i] + k0[:, :, i // 2]) / 2)
    for g in range(2):
        mean = (q0[:, :, 2 * g] + q0[:, :, 2 * g + 1]) / 2
        close(k[:, :, g], zk[:, :, g] + (mean + k0[:, :, g]) / 2)
    tau = jnp.array([1.0, 2.0, 0.5, 3.0])
    now = jax.random.normal(key[4], (1, 3, 8))
    out_q, out_k, v = jax.jit(cca_ops.cca_mix)(
        q0, k0, now, now, jnp.zeros((48, 2)), jnp.zeros((6, 8, 8, 2)), tau)
    # with the convolutions at zero, z = 0: the norms of the mean alone
    norm = lambda a: np.linalg.norm(np.asarray(a), axis=-1)
    np.testing.assert_allclose(norm(out_q), np.sqrt(8) * np.asarray(
        tau)[None, None, :] * np.ones((1, 3, 4)), rtol=1e-5)
    np.testing.assert_allclose(norm(out_k), np.sqrt(8), rtol=1e-5)
    direction = np.asarray(q[:, :, 1] - zq[:, :, 1])
    close(out_q[:, :, 1], 2.0 * np.sqrt(8) * direction
          / np.linalg.norm(direction, axis=-1, keepdims=True))
    assert v.shape == (1, 3, 2, 8)


def test_top1_weight_is_the_chosen_probability():
    logits = jax.random.normal(jax.random.key(12), (10, 16))
    probs = jax.nn.softmax(logits, axis=-1)
    w, e, scores = moe.route_top_k(logits, None, 1, renormalize=False)
    np.testing.assert_array_equal(np.asarray(e[:, 0]),
                                  np.asarray(jnp.argmax(probs, -1)))
    close(w[:, 0], probs.max(-1), 1e-6)
    assert float(w.max()) < 1.0
    renormed, _, _ = moe.route_top_k(logits, None, 1)
    np.testing.assert_array_equal(np.asarray(renormed), 1.0)
    close(scores, probs, 1e-6)


# -- the router's state, carried --------------------------------------------------

def test_the_routers_state_passes_from_layer_to_layer(params):
    """With layer 0's experts adding nothing (its ``ffn_out_scale`` 0), a
    change to layer 0's router moves only its state: the output moves
    through layer 1's router iff layer 1's ``gamma`` is not 0. In both
    implementations."""
    x = tokens(13)
    quiet = jax.tree.map(lambda a: a, params)
    quiet["Block_0"] = {**params["Block_0"], "ffn_out_scale": jnp.zeros(32)}
    moved = {**quiet, "Block_0": {**quiet["Block_0"], "router_down": quiet[
        "Block_0"]["router_down"] * 3.0}}
    m = model()
    system = jax.jit(lambda p: m.apply({"params": p}, x))
    for f in (system, jax.jit(lambda p: ref.logits(p, x, ARCH))):
        assert float(jnp.abs(f(moved) - f(quiet)).max()) > 1e-5
        cut = lambda p: {**p, "Block_1": {**p["Block_1"],
                                          "router_eda": jnp.float32(0.0)}}
        np.testing.assert_allclose(np.asarray(f(cut(moved))),
                                   np.asarray(f(cut(quiet))), atol=1e-6)
    h = jax.random.normal(jax.random.key(14), (2, 4, 32))
    r = jax.random.normal(jax.random.key(15), (2, 4, 16))
    p1 = params["Block_1"]
    assert float(jnp.abs(ref.router_logits(p1, h, r)[1]
                         - ref.router_logits(p1, h, 0 * r)[1]).max()) > 1e-4


# -- one chip's share of the experts ----------------------------------------------

def test_two_shares_and_the_common_path_add_up_to_the_uncut_layer(params):
    """Experts 0-3 on one chip, 4-7 on the other, each computing its part
    with the router over all 8; CCA, the router and the residual path, which
    both chips compute alike, counted once: together they are the uncut
    reference layer."""
    full = params["Block_0"]
    x = jax.random.normal(jax.random.key(16), (2, T, 32))
    r = jax.random.normal(jax.random.key(17), (2, T, 16))
    want_x, want_r, _, _ = jax.jit(lambda p, a, b: ref.layer(p, a, b, ARCH))(
        full, x, r)

    def share(offset, zero=False):
        arch = {**ARCH, "num_experts": 4, "num_routed_experts": 8,
                "expert_offset": offset}
        spec = arch_mod.layer_specs(arch)[0]
        block = Block(d_model=32, num_heads=4, d_ff=0,
                      compute_dtype=jnp.float32, seq_axis=None, spec=spec)
        held = {k: (v[offset:offset + 4] * (0 if zero else 1)
                    if k.startswith("moe_w") else v) for k, v in full.items()}
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, a, b: block.apply({"params": p}, a, b))(
                held, x, r)

    (a, ra), (b, rb), (common, rc) = share(0), share(4), share(0, zero=True)
    close(a + b - common, want_x, 1e-5)
    for got in (ra, rb, rc):
        close(got, want_r, 1e-5)


# -- the architecture's keys ----------------------------------------------------

def test_the_specs():
    specs = arch_mod.layer_specs(ARCH)
    assert len(specs) == 2 and specs[0] == specs[1]
    s = specs[0]
    assert s.mixers == ("cca", "ffn") and s.residual_scales
    assert s.cca == arch_mod.CCASpec(time0=2, time1=2)
    assert (s.num_heads, s.num_kv_heads, s.head_dim) == (4, 2, 16)
    assert s.router_hidden == 16
    assert (s.moe.top_k, s.moe.routed, s.moe.held, s.moe.renormalize,
            s.moe.routing_grad) == (1, 8, 8, False, False)
    assert s.rope == arch_mod.RopeSpec(theta=5e6, rotary_dim=8)
    assert s.norm_eps == 1e-5 and s.window is None
    # the other families' specs keep the defaults
    assert dataclasses.fields(arch_mod.LayerSpec)[-1].default is False


@pytest.mark.parametrize("change,word", [
    ({"layer_types": ["hybrid", "hybrid_sliding"]}, "hybrid_sliding"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"attention_bias": True}, "attention_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"partial_rotary_factor": 1.0}, "partial_rotary_factor"),
    ({"rope_parameters": {"hybrid": {"rope_theta": 1e4,
                                     "rope_type": "yarn"}}}, "rope_type"),
    ({"num_key_value_heads": 3}, "KV heads"),
    ({"zaya_mod": 1}, "zaya_mod"),
])
def test_what_is_not_built_raises(change, word):
    with pytest.raises(ValueError, match=word):
        arch_mod.layer_specs({**ARCH, **change})
