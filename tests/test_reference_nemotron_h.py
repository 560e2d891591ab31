"""The ``nemotron_h`` family (``TrainConfig.arch`` with
``hybrid_override_pattern``: one mixer a layer) against its plain reference.

Small sizes, seeded weights, float32 compute on the CPU: hidden 32, 8 Mamba-2
heads of 8 channels in 2 groups with a state of 16 and chunks of 8, 4 query
heads on 2 KV heads of 16, 16 experts top-3 of which a share holds 4, T = 30
(no multiple of the chunk). The reference is
``mpit_tpu/models/reference_nemotron_h.py``, which takes the recurrence step
by step; the system is ``TransformerLM`` with ``arch`` set.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import arch as arch_lib
from mpit_tpu.models import reference_nemotron_h as ref
from mpit_tpu.models.transformer import TransformerLM
from mpit_tpu.ops import moe
from mpit_tpu.ops.ssd import ssd

ARCH = {
    "hybrid_override_pattern": "MEMEM*EME", "num_hidden_layers": 9,
    "hidden_size": 32, "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "num_routed_experts": 16, "expert_offset": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "mlp_hidden_act": "relu2", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "tie_word_embeddings": False, "moe_row_bound": 96,
    "router_aux_loss_coef": 0.01,
}
VOCAB, T = 97, 30
SHARE = {"experts_held": 4, "expert_offset": 4}
LEAVES = {"M": 9, "E": 7, "*": 5}


def _arch(pattern, **more):
    return {**ARCH, "hybrid_override_pattern": pattern,
            "num_hidden_layers": len(pattern), **more}


def _model(arch, impl="xla", remat=False):
    return TransformerLM(vocab_size=VOCAB, arch=arch, attn_impl=impl,
                         remat=remat, compute_dtype=jnp.float32)


def _problem(arch):
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, VOCAB)
    targets = jax.random.randint(jax.random.key(2), (2, T), 0, VOCAB)
    params = jax.jit(_model(arch).init)(jax.random.key(0), tokens)["params"]
    # the seed gives a zero convolution bias; the comparison wants one
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.cos(jnp.arange(a.size, dtype=a.dtype))
        if "conv_b" in jax.tree_util.keystr(path) else a, params)
    return params, tokens, targets


@pytest.mark.parametrize("pattern,impl,remat", [
    ("M", "xla", False), ("E", "xla", False), ("*", "flash_force", False),
    ("MEMEM*EME", "xla", True), ("MEMEM*EME", "flash_force", False),
], ids=["mamba2_alone", "experts_alone", "attention_alone_kernels",
        "the_period_remat", "the_period_kernels"])
def test_system_matches_reference_on_loss_logits_and_every_gradient_leaf(
        pattern, impl, remat):
    arch = _arch(pattern)
    params, tokens, targets = _problem(arch)
    if impl == "flash_force":  # the kernels' tiles want T = 32
        tokens, targets = (jnp.pad(a, ((0, 0), (0, 2))) for a in
                           (tokens, targets))
    model = _model(arch, impl, remat)
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    want = jax.jit(lambda p: ref.logits(p, tokens, arch, **SHARE))(params)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_with_counters(p, tokens, targets),
        has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, **SHARE))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert len(flat) == len(ref_flat) == sum(LEAVES[k] for k in pattern) + 3
    for path, g in flat:
        r, name = ref_flat[path], jax.tree_util.keystr(path)
        if "moe_bias" in name:  # the choice is discrete: no gradient
            assert float(jnp.abs(g).max()) == float(jnp.abs(r).max()) == 0
            continue
        assert float(jnp.abs(r).max()) > 0, name  # every leaf is reached
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 2e-4, (name, err)
    assert ("ssm_chunk_log_decay_min" in counters) == ("M" in pattern)
    assert ("moe_rows_held" in counters) == ("E" in pattern)
    if "M" in pattern:
        assert float(counters["ssm_chunk_log_decay_min"]) < 0
    if "E" in pattern:
        assert float(counters["moe_rows_dropped"]) == 0


def test_reference_by_layer_is_the_reference():
    arch = _arch("MEMEM*EME", moe_routing_no_grad=True)
    params, tokens, targets = _problem(arch)
    loss, grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, **SHARE))(params)
    by_loss, by_grads, own = ref.loss_and_grad_by_layer(
        params, tokens, targets, arch, **SHARE)
    np.testing.assert_allclose(by_loss, loss, rtol=1e-6)
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_flatten_with_path(by_grads)[0],
            jax.tree_util.tree_flatten_with_path(grads)[0]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    assert [o is not None for o in own] == [k == "E" for k in "MEMEM*EME"]
    # given the reference's own choices back, nothing changes; its blocked
    # recurrence (T a multiple of the block) is the plain one
    again, _, _ = ref.loss_and_grad_by_layer(
        params, tokens, targets, arch, choices=own, **SHARE)
    np.testing.assert_allclose(again, by_loss, rtol=1e-6)
    x, dt, a, b, c, d = _scan_inputs(2 * ref.SCAN_BLOCK, groups=4)
    total = lambda blocks: lambda *v: jnp.sin(
        ref.recurrence(*v, blocks=blocks)).sum()
    plain = jax.grad(total(False), argnums=(0, 1, 3))(x, dt, a, b, c, d)
    blocked = jax.grad(total(True), argnums=(0, 1, 3))(x, dt, a, b, c, d)
    for p, q in zip(plain, blocked):
        np.testing.assert_allclose(p, q, rtol=1e-5, atol=1e-6)


def test_lower_precision_operands_move_the_reference():
    arch = _arch("MEMEM*EME")
    params, tokens, targets = _problem(arch)
    loss, grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, **SHARE))(params)
    low_loss, low = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, operand_dtype=jnp.bfloat16, **SHARE))(params)
    assert abs(float(low_loss) - float(loss)) > 1e-5
    err = lambda g, r: float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    assert err(low["Block_0"]["in_proj"], grads["Block_0"]["in_proj"]) > 1e-3


# -- the chunked scan against the recurrence -----------------------------------

def _scan_inputs(t, groups=2, heads=4, p=8, n=16, batch=2):
    ks = jax.random.split(jax.random.key(t), 5)
    x = jax.random.normal(ks[0], (batch, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, t, heads)))
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    b = jax.random.normal(ks[3], (batch, t, groups, n))
    c = jax.random.normal(ks[4], (batch, t, groups, n))
    return x, dt, a, b, c, jnp.linspace(0.5, 1.5, heads)


@pytest.mark.parametrize("t", [32, 45, 5], ids=[
    "a_multiple_of_the_chunk", "not_a_multiple", "under_one_chunk"])
def test_the_chunked_scan_is_the_recurrence_forward_and_gradient(t):
    x, dt, a, b, c, d = _scan_inputs(t)
    per_head = lambda v: jnp.repeat(v, x.shape[2] // v.shape[2], axis=2)
    with jax.default_matmul_precision("highest"):
        chunked = lambda *v: ssd(*v, chunk=16)[0]
        plain = lambda x, dt, a, b, c, d: ref.recurrence(
            x, dt, a, per_head(b), per_head(c), d)
        np.testing.assert_allclose(chunked(x, dt, a, b, c, d),
                                   plain(x, dt, a, b, c, d),
                                   rtol=1e-4, atol=1e-4)
        every = tuple(range(6))
        got = jax.grad(lambda *v: jnp.sin(chunked(*v)).sum(), every)(
            x, dt, a, b, c, d)
        want = jax.grad(lambda *v: jnp.sin(plain(*v)).sum(), every)(
            x, dt, a, b, c, d)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(g, w, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))


def test_the_scan_counts_its_deepest_chunk_and_a_state_not_carried_differs():
    x, dt, a, b, c, d = _scan_inputs(48)
    y, log_decay_min = ssd(x, dt, a, b, c, d, chunk=16)
    sums = (dt * a).reshape(2, 3, 16, 4).sum(2)
    assert float(log_decay_min) == pytest.approx(float(sums.min()), rel=1e-5)
    cut, _ = ssd(x, dt, a, b, c, d, chunk=16, carry_state=False)
    np.testing.assert_allclose(cut[:, :16], y[:, :16], rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(cut[:, 16:] - y[:, 16:]).max()) > 1e-2
    # a total decay far past float32's exponent range is no overflow here
    deep, low = ssd(x, 40.0 * dt, a, b, c, d, chunk=16)
    assert float(low) < -200 and bool(jnp.isfinite(deep).all())


# -- sigmoid routing ------------------------------------------------------------

def test_the_choice_follows_score_plus_bias_and_the_weights_the_score():
    key = jax.random.split(jax.random.key(3), 3)
    y = jax.random.normal(key[0], (64, 32))
    router = jax.random.normal(key[1], (32, 16)) / np.sqrt(32)
    bias = jnp.zeros(16).at[5].set(10.0).at[2].set(-10.0)
    weights, experts, probs = moe.route_top_k(y, router, 3, 2.5, bias)
    scores = jax.nn.sigmoid(y @ router)
    assert bool((experts == 5).any(-1).all())  # the bias puts 5 in every set
    assert not bool((experts == 2).any())  # and keeps 2 out of all
    np.testing.assert_array_equal(
        experts, jax.lax.top_k(scores + bias, 3)[1])
    chosen = jnp.take_along_axis(scores, experts, -1)  # without the bias
    np.testing.assert_allclose(
        weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(probs, scores / scores.sum(-1, keepdims=True),
                               rtol=1e-6)
    # no bias: the softmax family's routing, as it was
    w0, e0, p0 = moe.route_top_k(y, router, 3, 2.5)
    soft = jax.nn.softmax(y @ router)
    np.testing.assert_array_equal(e0, jax.lax.top_k(soft, 3)[1])
    np.testing.assert_allclose(p0, soft, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(-1), 2.5, rtol=1e-5)


def test_the_systems_choices_are_the_references_own():
    arch = _arch("E")
    params, tokens, _ = _problem(arch)
    params["Block_0"]["moe_bias"] = params["Block_0"]["moe_bias"].at[7].set(5.0)
    _, sown = jax.jit(lambda p: _model(arch).apply(
        {"params": p}, tokens, mutable=["routing", "counters"]))(params)
    chosen = sown["routing"]["Block_0"]["experts"][0].reshape(2, T, 3)
    x = params["Embed_0"]["embedding"][tokens]
    u = ref.rms_norm(x, params["Block_0"]["ffn_norm"], 1e-5)
    own = ref.own_choice(params["Block_0"], ref.router_scores(
        params["Block_0"], u), 3)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(own, -1))
    assert bool((chosen == 7).any(-1).all())


# -- the description ------------------------------------------------------------

def test_the_pattern_gives_one_mixer_a_layer():
    specs = arch_lib.layer_specs(ARCH)
    assert [s.mixers for s in specs] == [
        {"M": ("ssm",), "E": ("ffn",), "*": ("attention",)}[k]
        for k in "MEMEM*EME"]
    m, e, a = specs[0], specs[1], specs[5]
    assert m.ssm == arch_lib.SSMSpec(
        heads=8, head_dim=8, groups=2, state=16, conv_kernel=4, chunk=8,
        dt_min=0.001, dt_max=0.1, dt_floor=1e-4)
    assert (m.ssm.d_inner, m.ssm.conv_dim) == (64, 128) and m.moe is None
    assert e.moe == arch_lib.MoESpec(
        routed=16, held=4, offset=4, top_k=3, width=16, shared_width=32,
        scale=2.5, row_bound=96, routing_grad=True, scoring="sigmoid",
        expert="relu2")
    assert (a.num_heads, a.num_kv_heads, a.head_dim) == (4, 2, 16)
    assert a.rope is None and a.window is None and not a.gate
    assert {s.norm_eps for s in specs} == {1e-5} and e.ssm is a.ssm is None
    # a longer pattern is read from its start
    assert arch_lib.layer_specs({**ARCH, "num_hidden_layers": 3}) == specs[:3]


@pytest.mark.parametrize("change,word", [
    ({"hybrid_override_pattern": "ME-EM*EME"}, "layer kind '-'"),
    ({"n_group": 2}, "n_group"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"hybrid_override_pattern": "MEM"}, "9 layers"),
    ({"expert_offset": 14}, "experts 14..18 held of 16"),
])
def test_unbuilt_values_raise_by_name(change, word):
    with pytest.raises(ValueError, match=word.replace("(", r"\(")):
        arch_lib.layer_specs({**ARCH, **change})


def test_only_the_sync_trainer_takes_the_family():
    from mpit_tpu import run as program
    from mpit_tpu.utils.config import TrainConfig

    cfg = TrainConfig(model="transformer", algo="sync", arch=ARCH, seq_len=T)
    model = program._build_model(cfg, {"vocab_size": VOCAB}, worker_axis="dp")
    assert model.arch == ARCH and model.loss_with_counters is not None
    with pytest.raises(ValueError, match="only sync runs it"):
        program._build_model(dataclasses.replace(cfg, algo="easgd"),
                             {"vocab_size": VOCAB}, worker_axis="dp")
    for field, value in (("decode", True), ("seq_axis", "sp"),
                         ("moe_experts", 4)):
        broken = TransformerLM(vocab_size=VOCAB, arch=ARCH, **{field: value})
        with pytest.raises(ValueError, match="not built"):
            broken.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
