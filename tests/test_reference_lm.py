"""The described block (``TrainConfig.arch``) against its plain reference.

Small sizes, seeded weights, float32 compute on the CPU: 2 KV heads, 4 query
heads on the full layers and 6 on the sliding ones, head_dim 16, 16 experts
top-3 of which a share holds 4, window 8, T = 32. The reference is
``mpit_tpu/models/reference_lm.py``; the system is ``TransformerLM`` with
``arch`` set, through the dense branch (``attn_impl="xla"``) and through the
Pallas kernels in interpret mode (``flash_force``).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import arch as arch_lib
from mpit_tpu.models import reference_lm as ref
from mpit_tpu.models import reference_nemotron_h as ref_h
from mpit_tpu.models.transformer import TransformerLM, aggregate_counters
from mpit_tpu.ops import moe

fa = importlib.import_module("mpit_tpu.ops.flash_attention")

ARCH = {
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_routed_experts": 16,
    "expert_offset": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 8,
    "moe_routed_scaling_factor": 2.5, "moe_row_bound": 96,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6],
}
VOCAB, T = 97, 32
SHARE = {"experts_held": 4, "expert_offset": 4}


def _model(impl="xla", remat=False, **arch):
    return TransformerLM(vocab_size=VOCAB, arch={**ARCH, **arch},
                         attn_impl=impl, remat=remat,
                         compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def problem():
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, VOCAB)
    targets = jax.random.randint(jax.random.key(2), (2, T), 0, VOCAB)
    params = jax.jit(_model().init)(jax.random.key(0), tokens)["params"]
    return params, tokens, targets


def _reference(params, tokens, targets, **kw):
    """``ref.loss_and_grad`` under jit (the arch is not hashable)."""
    return jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, ARCH, **SHARE, **kw))(params)


@pytest.fixture(scope="module")
def reference(problem):
    """The reference's loss and gradient on the problem, computed once."""
    return _reference(*problem)


def _system_loss(model, params, tokens, targets):
    logits = model.apply({"params": params}, tokens)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


@pytest.mark.parametrize("impl,remat,chunk", [
    ("xla", False, None), ("flash_force", True, None),
    ("xla", True, 32),  # the expert layers walk their 96 rows in chunks
])
def test_system_matches_reference_on_loss_logits_and_every_gradient_leaf(
    problem, reference, impl, remat, chunk, monkeypatch
):
    if chunk:
        monkeypatch.setattr(moe, "chunk_rows", lambda *a: chunk)
    params, tokens, targets = problem
    model = _model(impl, remat)
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    want = jax.jit(lambda p: ref.logits(p, tokens, ARCH, **SHARE))(params)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(model, p, tokens, targets)))(params)
    ref_loss, ref_grads = reference
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert len(flat) == len(ref_flat) == 10 + 4 * 14 + 3
    for path, g in flat:
        r = ref_flat[path]
        assert float(jnp.abs(r).max()) > 0, path  # every leaf is reached
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 2e-4, (jax.tree_util.keystr(path), err)


def test_reference_by_layer_is_the_reference(problem, reference):
    params, tokens, targets = problem
    loss, grads = reference
    loss2, grads2, own = ref.loss_and_grad_by_layer(
        params, tokens, targets, ARCH, **SHARE, to_host=True)
    np.testing.assert_allclose(loss2, loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(grads2)):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
    # its own top-k, and the system's, are the same sets at float32
    _, sown = jax.jit(lambda p: _model().apply(
        {"params": p}, tokens, mutable=["routing"]))(params)
    for l in range(1, 5):
        mine = np.sort(np.asarray(own[l]).reshape(-1, 3), -1)
        theirs = np.sort(np.asarray(
            sown["routing"][f"Block_{l}"]["experts"][0]), -1)
        assert (mine == theirs).mean() > 0.99
    assert own[0] is None
    # given choices replace the top-k: another routing, another loss
    forced = [None] + [jnp.full((2, T, 3), 5, jnp.int32)] * 4
    assert abs(float(ref.loss(params, tokens, targets, ARCH, **SHARE,
                              choices=forced)) - float(loss)) > 1e-6


def test_lower_precision_operands_move_the_reference(problem, reference):
    params, tokens, targets = problem
    loss, grads = reference
    _, low = _reference(params, tokens, targets, operand_dtype=jnp.bfloat16)
    err = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
              for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(low)))
    assert 1e-4 < err < 0.5


@pytest.mark.parametrize("heads", [4, 6, 32])
@pytest.mark.parametrize("window", [8, None])
def test_windowed_grouped_kernel_matches_the_dense_mask(heads, window):
    """Interpret mode, forward and backward, 2 KV heads (groups of 2, 3
    and, as the nemotron_h family's attention has them, 16), tiles of 16
    and of 8 x 32 (a window inside one tile, and across three)."""
    keys = jax.random.split(jax.random.key(3), 4)
    q, ct = (jax.random.normal(k, (2, 64, heads, 16)) for k in keys[:2])
    k, v = (jax.random.normal(kk, (2, 64, 2, 16)) for kk in keys[2:])
    dense = jax.value_and_grad(
        lambda *a: (fa.masked_dense_attention(*a, window) * ct).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for bq, bk in ((16, 16), (8, 32), (32, 8)):
        got = jax.value_and_grad(
            lambda *a: (fa.flash_attention(
                *a, causal=True, window=window, use_pallas=True,
                block_q=bq, block_k=bk) * ct).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(dense)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_window_steps_cover_exactly_the_live_tiles():
    for t, bq, bk, w in ((64, 16, 16, 8), (64, 8, 32, 8), (64, 32, 8, 24),
                         (8192, 512, 512, 512), (8192, 256, 128, 512)):
        k_steps, q_steps = fa.window_steps(t, bq, bk, w)
        live = np.zeros((t // bq, t // bk), bool)
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        seen = (j <= i) & (j > i - w)
        for a in range(t // bq):
            for b in range(t // bk):
                live[a, b] = seen[a * bq:(a + 1) * bq,
                                  b * bk:(b + 1) * bk].any()
        assert k_steps == live.sum(1).max()
        assert q_steps == live.sum(0).max()


#: an expert layer of each family against its own reference: SwiGLU experts
#: behind softmax scores (``reference_lm``), ungated ``relu2`` experts behind
#: sigmoid scores with a bias on the choice (``reference_nemotron_h``)
FAMILIES = {
    "swiglu_softmax": (ref, ARCH, "swiglu", ref.swiglu),
    "relu2_sigmoid": (ref_h, {
        "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
        "moe_shared_expert_intermediate_size": 16}, "relu2", ref_h.relu2_mlp),
}
family = pytest.mark.parametrize("family", sorted(FAMILIES))


def _expert_layer(key, family, tokens=64, d=32, width=16, experts=16):
    ks = jax.random.split(key, 9)
    init = lambda k, *s: jax.random.normal(k, s) / np.sqrt(s[-2])
    p = {
        "moe_router": init(ks[0], d, experts) * 3,
        "moe_w_gate": init(ks[1], experts, d, width),
        "moe_w_up": init(ks[2], experts, d, width),
        "moe_w_down": init(ks[3], experts, width, d),
        "shared_w_gate": init(ks[4], d, width),
        "shared_w_up": init(ks[5], d, width),
        "shared_w_down": init(ks[6], width, d),
    }
    if FAMILIES[family][2] == "relu2":
        del p["moe_w_gate"], p["shared_w_gate"]
        p["moe_bias"] = 0.05 * jax.random.normal(ks[8], (experts,))
    return p, jax.random.normal(ks[7], (1, tokens, d))


def _shared(p, y, family):
    names = moe.EXPERTS[FAMILIES[family][2]][0]
    return FAMILIES[family][3](y, *(p[f"shared_{n}"] for n in names))


def _held_part(p, y, offset, held, row_bound, family):
    expert = FAMILIES[family][2]
    params = {"router": p["moe_router"],
              **{n: p[f"moe_{n}"][offset:offset + held]
                 for n in moe.EXPERTS[expert][0]}}
    if "moe_bias" in p:
        params["bias"] = p["moe_bias"]
    return moe.moe_ffn_held(params, y[0], top_k=3, expert_offset=offset,
                            row_bound=row_bound, scale=2.5, expert=expert)


def _cut(p, lo, hi):
    """The share of the layer's parameters that holds experts lo..hi."""
    return {n: (v[lo:hi] if n.startswith("moe_w") else v)
            for n, v in p.items()}


@family
def test_the_shares_add_up_to_the_uncut_layer(family):
    """16 experts as 4 shares of 4: the parts every share's chip computes,
    the shared expert counted once, sum to the uncut reference's output;
    so do the reference's own shares."""
    module, arch = FAMILIES[family][:2]
    p, y = _expert_layer(jax.random.key(4), family)
    whole = module.sparse_ffn(p, y, arch)
    shared = _shared(p, y, family)
    system, plain, rows = shared, shared, 0.0
    for offset in range(0, 16, 4):
        out, counters, _ = _held_part(p, y, offset, 4, 64 * 3, family)
        system = system + out[None]
        rows += float(counters["rows_held"])
        assert float(counters["rows_dropped"]) == 0
        plain = plain + module.sparse_ffn(
            _cut(p, offset, offset + 4), y, arch, experts_held=4,
            expert_offset=offset) - shared
    assert rows == 64 * 3  # every (token, choice) pair lands in one share
    np.testing.assert_allclose(system, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(plain, whole, rtol=1e-4, atol=1e-5)


@family
@pytest.mark.parametrize("tokens,experts,walked", [
    (64, 16, 192),  # the buffer is one chunk: a single pass
    (1024, 32, 3072),  # three chunks of 1,024, every one full
])
def test_forced_imbalance_drops_nothing_and_a_short_buffer_is_counted(
        tokens, experts, walked, family):
    """Every token sent to the same three experts, all held here: three
    rows a token against the 0.75 (or 0.375) uniform routing would send;
    nothing is dropped while the buffer holds them, and a buffer that does
    not counts what it lost."""
    module, arch = FAMILIES[family][:2]
    p, y = _expert_layer(jax.random.key(5), family, tokens=tokens,
                         experts=experts)
    p["moe_router"] = p["moe_router"].at[:, 4:7].add(
        100.0 * jnp.sign(y[0].mean(0))[:, None] / y.shape[-1])
    y = jnp.abs(y) * jnp.sign(y[0].mean(0))
    if "moe_bias" in p:  # a bias the forced scores' lead of 1e-4 outweighs
        p["moe_bias"] = 1e-4 * p["moe_bias"]
    out, counters, (_, chosen) = _held_part(p, y, 4, 4, tokens * 3, family)
    assert set(np.unique(chosen)) == {4, 5, 6}
    assert float(counters["rows_held"]) == tokens * 3
    assert float(counters["rows_walked"]) == walked
    assert float(counters["rows_dropped"]) == 0
    assert float(counters["load_max_over_mean"]) == pytest.approx(4 / 3)
    want = module.sparse_ffn(_cut(p, 4, 8), y, arch, experts_held=4,
                             expert_offset=4) - _shared(p, y, family)
    np.testing.assert_allclose(out[None], want, rtol=1e-4, atol=1e-5)
    _, short, _ = _held_part(p, y, 4, 4, tokens * 2, family)
    assert float(short["rows_dropped"]) == tokens
    assert float(short["rows_walked"]) == tokens * 2


def test_the_architecture_gives_the_layers_and_the_tree_it_gave():
    """What ``layer_specs`` and ``model.init`` gave this architecture
    before a layer could be one mixer alone (PR 32): attention then a
    feed-forward, the same description and the same parameter tree."""
    specs = arch_lib.layer_specs(ARCH)
    yarn = arch_lib.RopeSpec(500000.0, 8, yarn=(128.0, 8192, 32.0, 1.0),
                             attention_factor=1.4852030263919618)
    plain = arch_lib.RopeSpec(10000.0, 16)
    held = arch_lib.MoESpec(routed=16, held=4, offset=4, top_k=3, width=16,
                            shared_width=16, scale=2.5, row_bound=96)
    assert (held.routing_grad, held.scoring, held.expert) == (
        True, "softmax", "swiglu")
    layer = lambda heads, window, rope, d_ff, moe: arch_lib.LayerSpec(
        d_model=32, num_heads=heads, num_kv_heads=2, head_dim=16,
        window=window, rope=rope, gate=True, norm_eps=1e-6, d_ff=d_ff,
        moe=moe)
    assert specs == (
        layer(4, None, yarn, 64, None), layer(6, 8, plain, 0, held),
        layer(6, 8, plain, 0, held), layer(6, 8, plain, 0, held),
        layer(4, None, yarn, 0, held))
    assert {s.mixers for s in specs} == {("attention", "ffn")}
    assert {s.ssm for s in specs} == {None}
    tree = jax.eval_shape(_model().init, jax.random.key(0),
                          jnp.zeros((2, T), jnp.int32))["params"]
    shapes = lambda block: {n: l.shape for n, l in tree[block].items()}
    attention = lambda h: {
        "attn_norm": (32,), "ffn_norm": (32,), "wq": (32, h * 16),
        "wk": (32, 32), "wv": (32, 32), "wg": (32, h), "wo": (h * 16, 32)}
    assert shapes("Block_0") == {
        **attention(4), "w_gate": (32, 64), "w_up": (32, 64),
        "w_down": (64, 32)}
    sparse = {"moe_router": (32, 16), "moe_w_gate": (4, 32, 16),
              "moe_w_up": (4, 32, 16), "moe_w_down": (4, 16, 32),
              "shared_w_gate": (32, 16), "shared_w_up": (32, 16),
              "shared_w_down": (16, 32)}
    assert shapes("Block_1") == {**attention(6), **sparse}
    assert shapes("Block_4") == {**attention(4), **sparse}
    assert sorted(tree) == [f"Block_{l}" for l in range(5)] + [
        "Embed_0", "final_norm", "head"]


def test_counters_and_routing_are_sown(problem):
    params, tokens, _ = problem
    _, sown = jax.jit(lambda p: _model(remat=True).apply(
        {"params": p}, tokens, mutable=["counters", "routing"]))(params)
    counters = aggregate_counters(sown["counters"])
    assert set(counters) == {"moe_rows_held", "moe_rows_walked",
                             "moe_rows_tiled", "moe_load_max_over_mean",
                             "moe_rows_dropped", "moe_balance"}
    assert 3 <= float(counters["moe_balance"]) < 6  # top-k 3 = uniform
    assert float(counters["moe_rows_dropped"]) == 0
    assert 0 < float(counters["moe_rows_held"]) <= 96
    assert float(counters["moe_rows_walked"]) == 96  # one chunk: the bound
    # on the CPU the products are ragged_dot's, which walk the whole chunk
    assert float(counters["moe_rows_tiled"]) == 96
    assert sorted(sown["routing"]) == [f"Block_{l}" for l in range(1, 5)]


def test_unset_architecture_is_the_gpt2_parameter_tree():
    model = TransformerLM(vocab_size=VOCAB, num_layers=2, d_model=32,
                          num_heads=4, max_len=T)
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((2, T), jnp.int32))["params"]
    paths = sorted(jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(tree)[0])
    block = lambda i: [
        f"['Block_{i}']['Dense_{k}']['{leaf}']" for k, leaves in
        enumerate((["kernel"], ["kernel"], ["bias", "kernel"],
                   ["bias", "kernel"])) for leaf in leaves
    ] + [f"['Block_{i}']['LayerNorm_{k}']['{leaf}']"
         for k in range(2) for leaf in ("bias", "scale")]
    assert paths == sorted(
        block(0) + block(1) + ["['Embed_0']['embedding']",
                               "['LayerNorm_0']['bias']",
                               "['LayerNorm_0']['scale']",
                               "['pos_embedding']"])


@pytest.mark.parametrize("field,value", [("decode", True),
                                         ("seq_axis", "sp"),
                                         ("moe_experts", 4)])
def test_unbuilt_paths_raise_for_an_architecture(field, value):
    model = TransformerLM(vocab_size=VOCAB, arch=ARCH, **{field: value})
    with pytest.raises(ValueError, match="architecture"):
        jax.eval_shape(model.init, jax.random.key(0),
                       jnp.zeros((2, T), jnp.int32))


def test_routing_without_gradient_is_the_references_too(problem, reference):
    """``moe_routing_no_grad``: 0 into every ``moe_router`` in the system
    and the reference alike, and every other leaf equal between the two
    (and not what it is with the gradient, which passes into the layer's
    input)."""
    params, tokens, targets = problem
    arch = {**ARCH, "moe_routing_no_grad": True}
    model = _model(moe_routing_no_grad=True)
    grads = jax.jit(jax.grad(
        lambda p: _system_loss(model, p, tokens, targets)))(params)
    _, ref_grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, **SHARE))(params)
    _, with_grad = reference
    changed = 0
    for (path, g), (_, r), (_, w) in zip(*(
            jax.tree_util.tree_flatten_with_path(t)[0]
            for t in (grads, ref_grads, with_grad))):
        name = jax.tree_util.keystr(path)
        if name.endswith("['moe_router']"):
            assert not np.any(g) and not np.any(r) and np.any(w), name
            continue
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < 2e-4, (name, err)
        changed += float(jnp.linalg.norm(w - r) / jnp.linalg.norm(r)) > 1e-3
    assert changed > 10  # the path into the layers' inputs is gone as well


def test_only_the_sync_trainer_takes_an_architecture():
    from mpit_tpu import run as program
    from mpit_tpu.utils.config import TrainConfig

    for algo in ("easgd", "zero-sync", "downpour", "moe-sync"):
        cfg = TrainConfig(model="transformer", algo=algo, arch=ARCH)
        with pytest.raises(ValueError, match="only sync"):
            program._build_model(cfg, {"vocab_size": VOCAB})
    model = program._build_model(
        TrainConfig(model="transformer", algo="sync", arch=ARCH),
        {"vocab_size": VOCAB})
    assert callable(model.loss_with_counters)
    assert TransformerLM(vocab_size=VOCAB).loss_with_counters is None


def test_the_balance_loss_is_the_references_whole_and_by_layer(problem):
    """``router_aux_loss_coef``: the model's ``loss_with_counters`` (what the
    sync step differentiates) against the reference with the same term,
    whole and a layer at a time, beside ``moe_routing_no_grad``: the router
    then learns from the balance term alone."""
    params, tokens, targets = problem
    extra = {"moe_routing_no_grad": True, "router_aux_loss_coef": 0.5}
    arch = {**ARCH, **extra}
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        _model(**extra).loss_with_counters, has_aux=True))(
            params, tokens, targets)
    ref_loss, ref_grads = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, arch, **SHARE))(params)
    plain, _ = jax.jit(lambda p: ref.loss_and_grad(
        p, tokens, targets, {**ARCH, "moe_routing_no_grad": True},
        **SHARE))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(
        ref_loss - plain, 0.5 * counters["moe_balance"], rtol=1e-3)
    loss2, grads2, _ = ref.loss_and_grad_by_layer(
        params, tokens, targets, arch, **SHARE)
    np.testing.assert_allclose(loss2, ref_loss, rtol=1e-6)
    for (path, g), (_, r), (_, r2) in zip(*(
            jax.tree_util.tree_flatten_with_path(t)[0]
            for t in (grads, ref_grads, grads2))):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(r).max()) > 0, name  # the router learns again
        for got in (g, r2):
            err = float(jnp.linalg.norm(got - r) / jnp.linalg.norm(r))
            assert err < 2e-4, (name, err)
