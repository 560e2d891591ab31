"""The ``olmo_hybrid`` family (``TrainConfig.arch`` with ``layer_types`` of
``linear_attention`` and ``full_attention``) against its plain reference.

Small sizes, seeded weights, float32 compute on the CPU: hidden 32, 4
gated-delta-rule heads with keys of 8 and values of 16 in chunks of 8, 4
attention heads of 8 on 4 KV heads, SwiGLU 48 wide, T = 30 (no multiple of the
chunk). The reference is ``mpit_tpu/models/reference_olmo_hybrid.py``, which
takes the recurrence step by step; the system is ``TransformerLM`` with
``arch`` set.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mpit_tpu.models import arch as arch_lib
from mpit_tpu.models import reference_olmo_hybrid as ref
from mpit_tpu.models.transformer import TransformerLM

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
ARCH = {
    "norm_at": "output", "qk_norm": True,  # this repo's keys: OLMo 2's block
    "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "layer_types": PERIOD,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "linear_chunk_size": 8, "rope_parameters": {"rope_theta": None},
}
VOCAB, T = 97, 30
LEAVES = {"linear_attention": 14 + 4, "full_attention": 7 + 4}
GROUPS = {
    "norms": ["linattn_norm", "attn_norm", "ffn_norm", "final_norm",
              "gate_norm", "q_norm", "k_norm"],
    "linattn": ["lin_q", "lin_k", "lin_v", "lin_gate", "lin_o", "lin_a",
                "lin_b", "conv_q", "conv_k", "conv_v", "A_log", "dt_bias"],
    "attention": ["wq", "wk", "wv", "wo"],
    "mlp": ["w_gate", "w_up", "w_down"],
    "embedding": ["embedding"], "head": ["head"],
}


def _arch(kinds, **more):
    return {**ARCH, "layer_types": list(kinds), "num_hidden_layers": len(kinds),
            **more}


def _model(arch, impl="xla", remat=False):
    return TransformerLM(vocab_size=VOCAB, arch=arch, attn_impl=impl,
                         remat=remat, compute_dtype=jnp.float32)


def _problem(arch, t=T):
    tokens = jax.random.randint(jax.random.key(1), (2, t), 0, VOCAB)
    targets = jax.random.randint(jax.random.key(2), (2, t), 0, VOCAB)
    params = jax.jit(_model(arch).init)(jax.random.key(0), tokens)["params"]
    # the seed gives norm scales of 1; the comparison wants them told apart
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.2 * jnp.cos(jnp.arange(a.size, dtype=a.dtype))
        if "norm" in jax.tree_util.keystr(path) else a, params)
    return params, tokens, targets


def _by_group(grads, ref_grads):
    """``|g - g_reference| / |g_reference|`` by leaf group."""
    group_of = {leaf: g for g, leaves in GROUPS.items() for leaf in leaves}
    sums = {}
    for (path, g), (_, r) in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_flatten_with_path(ref_grads)[0]):
        leaf = jax.tree_util.keystr(path).rstrip("]'").rsplit("'", 1)[-1]
        have = sums.setdefault(group_of[leaf], [0.0, 0.0])
        have[0] += float(jnp.sum(jnp.square(g - r)))
        have[1] += float(jnp.sum(jnp.square(r)))
    return {g: (d / n) ** 0.5 for g, (d, n) in sums.items()}


@pytest.mark.parametrize("kinds,impl,remat", [
    (["linear_attention"], "xla", False),
    (["full_attention"], "xla", False),
    (["full_attention"], "flash_force", False),
    (PERIOD, "xla", True),
    (PERIOD, "flash_force", False),
], ids=["linear_attention_alone", "full_attention_alone",
        "full_attention_alone_kernels", "the_period_remat",
        "the_period_kernels"])
def test_system_matches_reference_on_loss_logits_and_every_gradient_leaf(
        kinds, impl, remat):
    arch = _arch(kinds)
    # the kernels' tiles want T = 32
    params, tokens, targets = _problem(arch, 32 if impl == "flash_force" else T)
    model = _model(arch, impl, remat)
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    want = jax.jit(lambda p: ref.logits(p, tokens, arch))(params)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_with_counters(p, tokens, targets),
        has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert len(flat) == len(ref_flat) == sum(LEAVES[k] for k in kinds) + 3
    for path, g in flat:
        r, name = ref_flat[path], jax.tree_util.keystr(path)
        assert float(jnp.abs(r).max()) > 0, name  # every leaf is reached
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 3e-4, (name, err)
    assert set(_by_group(grads, ref_grads)) <= set(GROUPS)
    assert ("delta_chunk_log_decay_min" in counters) == (
        "linear_attention" in kinds)
    if "linear_attention" in kinds:
        assert float(counters["delta_chunk_log_decay_min"]) < 0


def test_one_adamw_move_is_the_references():
    arch = _arch(PERIOD)
    params, tokens, targets = _problem(arch)
    model = _model(arch)
    opt = optax.adamw(3e-4, weight_decay=1e-4)
    _, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_with_counters(p, tokens, targets),
        has_aux=True))(params)
    _, ref_grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch))(params)
    move = lambda g: optax.apply_updates(
        params, opt.update(g, opt.init(params), params)[0])
    diff = jax.tree.map(lambda a, b: a - b, move(grads), move(ref_grads))
    moved = jax.tree.map(lambda a, b: a - b, move(ref_grads), params)
    norm = lambda t: float(optax.global_norm(t))
    assert norm(moved) > 0
    # a sign step: it differs only where rounding flips a gradient near 0
    assert norm(diff) / norm(moved) < 0.05


@pytest.mark.parametrize("change,moves", [
    ({"norm_at": "input"}, "the norm at the input"),
    ({"qk_norm": False}, "no QK norm"),
    ({"linear_allow_neg_eigval": False}, "beta in (0, 1)"),
], ids=["output_norm", "qk_norm", "neg_eigval"])
def test_each_assumed_piece_of_the_block_matters(change, moves):
    """A model built the other way disagrees with the reference by far more
    than rounding: the comparison sees the norm's place, the QK norm and the
    negative eigenvalues."""
    arch = _arch(PERIOD)
    params, tokens, targets = _problem(arch)
    other = _model({**arch, **change})
    shapes = jax.eval_shape(other.init, jax.random.key(0), tokens)["params"]
    # the same weights where the other model has the leaf
    given = jax.tree_util.tree_map_with_path(
        lambda path, a: _leaf_at(params, path), shapes)
    want = ref.loss(params, tokens, targets, arch)
    got = other.loss_with_counters(given, tokens, targets)[0]
    assert abs(float(got) - float(want)) > 1e-3 * float(want), moves
    same = _model(arch).loss_with_counters(params, tokens, targets)[0]
    np.testing.assert_allclose(same, want, rtol=1e-5)


def _leaf_at(tree, path):
    for key in path:
        tree = tree[key.key]
    return tree


def test_reference_by_layer_is_the_reference():
    arch = _arch(PERIOD)
    params, tokens, targets = _problem(arch)
    loss, grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch))(params)
    by_loss, by_grads, own = ref.loss_and_grad_by_layer(
        params, tokens, targets, arch, experts_held=0, expert_offset=0,
        choices=[None] * 4, to_host=True)
    np.testing.assert_allclose(by_loss, loss, rtol=1e-6)
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_flatten_with_path(by_grads)[0],
            jax.tree_util.tree_flatten_with_path(grads)[0]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    assert own == [None] * 4
    # the blocked recurrence (T a multiple of the block) is the plain one
    keys = jax.random.split(jax.random.key(4), 5)
    t = 2 * ref.SCAN_BLOCK
    q, k = (jax.random.normal(kk, (1, t, 2, 4)) / 2 for kk in keys[:2])
    v = jax.random.normal(keys[2], (1, t, 2, 6))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, t, 2)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, 2)))
    total = lambda blocks: lambda *a: jnp.sin(
        ref.recurrence(*a, blocks=blocks)).sum()
    plain = jax.grad(total(False), argnums=range(5))(q, k, v, g, beta)
    blocked = jax.grad(total(True), argnums=range(5))(q, k, v, g, beta)
    for a, b in zip(plain, blocked):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_lower_precision_operands_move_the_reference():
    arch = _arch(PERIOD)
    params, tokens, targets = _problem(arch)
    _, grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch))(params)
    _, low = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, operand_dtype=jnp.float8_e4m3fn))(params)
    errs = _by_group(low, grads)
    assert set(errs) == set(GROUPS)
    assert all(err > 0.02 for err in errs.values()), errs


def test_the_delta_term_left_out_moves_the_reference():
    """Plain gated linear attention under the model's name: the third control
    of ``scripts/olmo_hybrid_controls.py``."""
    arch = _arch(PERIOD)
    params, tokens, targets = _problem(arch)
    whole = ref.loss(params, tokens, targets, arch)
    without = ref.loss(params, tokens, targets, arch, delta_term=False)
    assert abs(float(whole) - float(without)) > 1e-4 * float(whole)


def test_specs_of_the_family():
    specs = arch_lib.layer_specs(ARCH)
    assert [s.mixers for s in specs] == [("linear_attention", "ffn")] * 3 + [
        ("attention", "ffn")]
    lin, full = specs[0], specs[3]
    assert lin.linattn == arch_lib.LinearAttentionSpec(
        heads=4, key_dim=8, value_dim=16, conv_kernel=4, neg_eigval=True,
        chunk=8)
    assert lin.norm_at == full.norm_at == "output" and lin.d_ff == 48
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (4, 4, 8)
    assert full.rope is None and full.qk_norm and full.window is None
    assert full.linattn is None and not lin.qk_norm
    # an arch without the two keys norms the input and has no QK norm,
    # whatever family it names: no model's name is read
    plain = arch_lib.layer_specs({
        **{k: v for k, v in ARCH.items() if k not in ("norm_at", "qk_norm")},
        "model_type": "olmo_hybrid", "layer_types": ["full_attention"] * 4,
        "rope_parameters": {"rope_theta": 10000.0}})
    assert {(s.norm_at, s.qk_norm) for s in plain} == {("input", False)}
    assert plain[0].rope == arch_lib.RopeSpec(10000.0, 8)
    assert dataclasses.replace(plain[0], norm_at="output") != plain[0]


@pytest.mark.parametrize("change,word", [
    ({"linear_num_value_heads": 8}, "value heads in groups"),
    ({"linear_chunk_size": 12}, "power of two"),
    ({"norm_at": "middle"}, "norm_at"),
    ({"layer_types": ["linear_attention", "chunked_attention"] * 2},
     "chunked_attention"),
], ids=["grouped_value_heads", "chunk", "norm_at", "layer_type"])
def test_what_is_not_built_raises_by_name(change, word):
    with pytest.raises(ValueError, match=word):
        arch_lib.layer_specs({**ARCH, **change})


def test_the_family_trains_under_sync_only():
    from mpit_tpu.run import _build_model
    from mpit_tpu.utils.config import TrainConfig

    cfg = TrainConfig(model="transformer", algo="easgd", arch=ARCH, seq_len=T)
    with pytest.raises(ValueError):
        _build_model(cfg, {"vocab_size": VOCAB}, worker_axis="dp")
