"""The Pallas kernels of the main path, compiled for a described TPU v5e.

The chip's compiler is installed where there is no chip: it compiles for a
topology that is described and not attached, and raises what the chip would
raise — a tile not aligned to Mosaic's (8, 128) tiling, more VMEM than a
kernel may take — which interpret mode never sees. Nothing runs, so these
say nothing about results or times.

Only one process may load the TPU's library, so the topology is described
inside a fixture (never at import) and every such compile lives in this one
file; where no topology can be described the tests skip.
"""

import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

fa = importlib.import_module("mpit_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to jax's persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "shape,dtype",
    [
        # gpt2s_easgd_1chip_flash: GPT-2-small, batch 8
        ((8, 1024, 12, 64), jnp.bfloat16),
        # ptb-transformer-large; chip_smoke leg E
        ((2, 512, 12, 64), jnp.bfloat16),
        # a T with no 128-multiple divisor but itself
        ((2, 384, 12, 64), jnp.bfloat16),
        # twice the length, D = 128
        ((1, 2048, 8, 128), jnp.bfloat16),
        # the chooser's largest estimate: float32 tiles at D = 128
        ((1, 1024, 4, 128), jnp.float32),
        # and from 8,192 tokens on, where all three kernels take 1,024
        ((1, 8192, 2, 128), jnp.float32),
    ],
)
def test_flash_kernels_compile_with_the_chosen_tiles(
    shape, dtype, one_chip, no_compile_cache
):
    """Forward, dQ and dK/dV at the tiles ``choose_blocks`` gives the
    shape, causal: three Mosaic kernels in the compiled step."""
    _, t, _, d = shape
    blocks = fa.choose_blocks(t, d, dtype)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        out = fa._flash(q, k, v, True, blocks, False)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x
    ).compile()
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") == 3


@pytest.mark.parametrize(
    "heads,kv_heads,window,calls",
    [
        # laguna_s21_sync_1chip_8k's sliding layers: 72 query heads over 8
        # KV heads, window 512
        (72, 8, 512, 3),
        # and its full layers: 48 query heads, causal
        (48, 8, None, 3),
        # nemotron3_nano_sync_1chip_8k's attention layer: 32 query heads
        # over 2 KV heads, a group of 16
        (32, 2, None, 3),
    ],
)
def test_grouped_and_windowed_kernels_compile_at_8k(
    heads, kv_heads, window, calls, one_chip, no_compile_cache
):
    """T = 8,192, D = 128, bfloat16, at the chosen tiles: forward, dQ and
    the dK/dV kernel that sums over each KV head's group, with the index
    maps that walk only the window's tiles."""
    t, d = 8192, 128
    blocks = fa.choose_blocks(t, d, jnp.bfloat16, window)
    q = jax.ShapeDtypeStruct((1, t, heads, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, t, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = fa._flash(q, k, v, True, blocks, False, window)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv
    ).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == calls
    names = ("flash_window_fwd", "flash_window_dq", "flash_window_dkv") \
        if window else ("flash_fwd", "flash_dq", "flash_dkv")
    for name in names:  # the trace's per-kernel metrics read these names
        assert f"%{name}." in text or f"%{name} " in text, name


def test_remat_block_keeps_the_kernels_residuals_at_8k(
    one_chip, no_compile_cache, monkeypatch
):
    """The gradient of a two-layer described model under ``remat`` at
    8,192 tokens, one window layer (72 heads) and one full layer (48), as
    ``laguna_s21_sync_1chip_8k`` has them (hidden 1,024 and a thin FFN, to
    keep the compile short: what the block keeps depends on the heads
    alone). One forward kernel a layer, where a remat with no policy runs
    it twice, and no more scratch than that remat's plus the bytes kept:
    bf16 ``out`` and the roped ``q`` 151.0 + 151.0 MB (window) and 100.7 +
    100.7 MB (full), ``k`` and ``v`` 33.6 MB a layer, f32 ``lse`` 2.4 + 1.6
    MB: 574,357,504 bytes. (Read here: 63 MB more; the recomputation it
    spares had its own scratch.)"""
    import flax.linen as nn

    from mpit_tpu.models import transformer

    # on the CPU platform flash_force means interpret mode; the kernels
    # have to compile for the described chip
    monkeypatch.setattr(fa, "pallas_interpret", lambda: False)
    t = 8192
    arch = {
        "hidden_size": 1024, "intermediate_size": 512, "num_hidden_layers": 2,
        "num_key_value_heads": 8, "head_dim": 128, "sliding_window": 512,
        "layer_types": ["sliding_attention", "full_attention"],
        "num_attention_heads_per_layer": [72, 48], "gating": "per-head",
        "rope_parameters": {"rope_theta": 10000},
    }
    kept = sum(t * (2 * h + 2 * 8) * 128 * 2 + h * t * 4 for h in (72, 48))
    assert kept == 574_357_504
    model = transformer.TransformerLM(
        vocab_size=256, arch=arch, attn_impl="flash_force", remat=True)
    on_chip = lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.clone(attn_impl="xla").init(
            jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"]))
    tokens = on_chip(jax.ShapeDtypeStruct((1, t), jnp.int32))

    def compiled():
        return jax.jit(jax.grad(lambda p, x: jnp.square(
            model.apply({"params": p}, x)).mean())).lower(
                params, tokens).compile()

    def kernels(text):
        return {name: len(re.findall(
            rf"%{name}[. ][^\n]*custom_call_target=\"tpu_custom_call\"", text))
            for name in ("flash_window_fwd", "flash_window_dq",
                         "flash_window_dkv", "flash_fwd", "flash_dq",
                         "flash_dkv")}

    step = compiled()
    assert set(kernels(step.as_text()).values()) == {1}
    monkeypatch.setattr(
        transformer, "_RematBlock", nn.remat(transformer.Block))
    plain = compiled()
    assert kernels(plain.as_text()) == {
        "flash_window_fwd": 2, "flash_window_dq": 1, "flash_window_dkv": 1,
        "flash_fwd": 2, "flash_dq": 1, "flash_dkv": 1}
    grown = (step.memory_analysis().temp_size_in_bytes
             - plain.memory_analysis().temp_size_in_bytes)
    assert grown <= kept, grown


def test_remat_mamba2_block_compiles_at_8k(
        one_chip, no_compile_cache, monkeypatch):
    """The gradient of one remat'd Mamba-2 layer at the published widths
    (hidden 2,688, 64 heads of 64, 8 groups, state 128, chunks of 128) and
    8,192 tokens, as ``nemotron3_nano_sync_1chip_8k`` has four of them, with
    the recurrence's kernels compiled for the described chip: one ``ssd_bwd``
    a layer, and as many ``ssd_fwd`` as the remat's policy makes them (one
    where ``_REMAT_KEEPS`` holds ``ssd_out``, the kernel's output and
    entering states; two where they are computed again). The trace's
    metrics read the calls by these names. Scratch under 3 GiB."""
    from mpit_tpu.models import transformer
    from mpit_tpu.ops import ssd as ssd_ops

    # on the CPU platform the choice falls to the jax.numpy form and a
    # kernel asked for is interpreted; here they compile for the chip
    monkeypatch.setattr(ssd_ops, "pallas_supported", lambda: True)
    monkeypatch.setattr(ssd_ops, "pallas_interpret", lambda: False)
    t = 8192
    arch = {
        "hybrid_override_pattern": "M", "num_hidden_layers": 1,
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
        "chunk_size": 128,
    }
    model = transformer.TransformerLM(vocab_size=256, arch=arch, remat=True)
    on_chip = lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 16), jnp.int32))["params"]))
    assert params["Block_0"]["in_proj"].shape == (2688, 10304)
    tokens = on_chip(jax.ShapeDtypeStruct((1, t), jnp.int32))
    compiled = jax.jit(jax.grad(lambda p, x: model.loss_with_counters(
        p, x, x)[0])).lower(params, tokens).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        rf"%{name}[. ][^\n]*custom_call_target=\"tpu_custom_call\"", text))
        for name in ("ssd_fwd", "ssd_bwd")}
    kept = "ssd_out" in transformer._REMAT_KEEPS
    assert calls == {"ssd_fwd": 1 if kept else 2, "ssd_bwd": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


def test_remat_linear_attention_block_compiles_at_8k(
        one_chip, no_compile_cache, monkeypatch):
    """The gradient of one remat'd gated-delta-rule layer at the published
    widths (hidden 3,840, 30 heads with keys of 96 and values of 192, chunks
    of 64, SwiGLU 11,008, the norms on the sublayers' outputs) and 8,192
    tokens, as ``olmo_hybrid_sync_1chip_8k`` has three of them, with the
    recurrence's kernels compiled for the described chip: one ``delta_bwd`` a
    layer, and as many ``delta_fwd`` as the remat's policy makes them (two
    where nothing of the mixer is kept: the forward and the recomputation,
    whose entering states the backward reads; one if ``_REMAT_KEEPS`` came to
    hold the kernel's output and states). The trace's metrics read the calls
    by these names, under the four scopes. Scratch under 2.25 GiB beside the
    layer's gradient, which is under the ``jax.numpy`` form's (2.32 GiB
    compiled here in PR 35, segments under a ``jax.checkpoint`` and all): the
    chunk's matrices never leave VMEM."""
    from mpit_tpu.models import transformer
    from mpit_tpu.ops import gated_delta as delta_ops

    # on the CPU platform the choice falls to the jax.numpy form and a
    # kernel asked for is interpreted; here they compile for the chip
    monkeypatch.setattr(delta_ops, "pallas_supported", lambda: True)
    monkeypatch.setattr(delta_ops, "pallas_interpret", lambda: False)
    t = 8192
    arch = {
        "norm_at": "output", "num_hidden_layers": 1,
        "layer_types": ["linear_attention"], "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    model = transformer.TransformerLM(vocab_size=256, arch=arch, remat=True)
    on_chip = lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 16), jnp.int32))["params"]))
    assert params["Block_0"]["lin_v"].shape == (3840, 5760)
    assert params["Block_0"]["conv_k"].shape == (2880, 4)
    tokens = on_chip(jax.ShapeDtypeStruct((1, t), jnp.int32))
    compiled = jax.jit(jax.grad(lambda p, x: model.loss_with_counters(
        p, x, x)[0])).lower(params, tokens).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        rf"%{name}[. ][^\n]*custom_call_target=\"tpu_custom_call\"", text))
        for name in ("delta_fwd", "delta_bwd")}
    kept = "delta_out" in transformer._REMAT_KEEPS
    assert calls == {"delta_fwd": 1 if kept else 2, "delta_bwd": 1}
    for scope in ("linattn", "linattn_conv", "delta_rule", "linattn_gate"):
        assert f"/{scope}/" in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 2.25 * 2 ** 30


def test_remat_cca_block_compiles_at_8k(one_chip, no_compile_cache,
                                        monkeypatch):
    """The gradient of one remat'd compressed-convolutional-attention layer
    and its expert layer at the published widths (hidden 2,048, 8 query and 2
    KV heads of 128 in the latent, two taps each convolution, a 256-wide MLP
    router with its carried state, 8 of 16 SwiGLU experts of 2,048, top-1)
    and 8,192 tokens, as ``zaya1_sync_1chip_8k`` has six of them, with the
    grouped causal flash kernels compiled for the described chip: one of
    each, the forward's output kept by the remat. The trace's metrics read
    the mixing and the router under these scopes. Scratch under 2 GiB beside
    the layer's gradient."""
    from mpit_tpu.models import transformer

    monkeypatch.setattr(fa, "pallas_supported", lambda: True)
    monkeypatch.setattr(fa, "pallas_interpret", lambda: False)
    t = 8192
    arch = {
        "num_hidden_layers": 1, "layer_types": ["hybrid"],
        "hidden_size": 2048, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 128, "cca_time0": 2,
        "cca_time1": 2, "router_hidden_size": 256, "num_experts": 8,
        "num_routed_experts": 16, "num_experts_per_tok": 1,
        "moe_intermediate_size": 2048, "moe_row_bound": 8192,
        "moe_routing_no_grad": True, "router_aux_loss_coef": 0.001,
        "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
        "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                       "rope_theta": 5000000}},
    }
    model = transformer.TransformerLM(vocab_size=256, arch=arch, remat=True,
                                      attn_impl="flash")
    on_chip = lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 128), jnp.int32))["params"]))
    assert params["Block_0"]["cca_conv1"].shape == (10, 128, 128, 2)
    assert params["Block_0"]["moe_w_up"].shape == (8, 2048, 2048)
    tokens = on_chip(jax.ShapeDtypeStruct((1, t), jnp.int32))
    compiled = jax.jit(jax.grad(lambda p, x: model.loss_with_counters(
        p, x, x)[0])).lower(params, tokens).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        rf"%{name}[. ][^\n]*custom_call_target=\"tpu_custom_call\"", text))
        for name in ("flash_fwd", "flash_dq", "flash_dkv")}
    assert calls == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}
    for scope in ("cca_mix", "rope", "router_mlp", "moe_router",
                  "moe_experts", "attention"):
        assert f"/{scope}/" in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30


@pytest.mark.parametrize("rows_per_group,rows,d,width", [
    (320, 5120, 3072, 1024),  # laguna_s21_sync_1chip_8k: a 5,120-row chunk
    (384, 6144, 2688, 2048),  # nemotron3_nano_sync_1chip_8k, width padded
    (512, 8192, 2048, 2048),  # zaya1_sync_1chip_8k: one 8,192-row pass
], ids=["laguna", "nemotron", "zaya"])
def test_grouped_kernels_compile_at_the_expert_cells_shapes(
        rows_per_group, rows, d, width, one_chip, no_compile_cache):
    """The held experts' grouped products (``ops/grouped``) into the width
    and out of it, forward and both cotangents, at the tiles
    ``choose_tiles`` gives each cell's shape: three Mosaic kernels a
    product, named after their scopes."""
    from mpit_tpu.ops import grouped

    on_chip = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    sizes = on_chip(8, dt=jnp.int32)
    for k, n in ((d, width), (width, d)):
        tiles = grouped.choose_tiles(rows_per_group, k, n, jnp.bfloat16)

        def loss(a, w, s):  # squared, so that the backward needs the output
            out = grouped._gmm(a, w, s, tiles, False)
            return (out.astype(jnp.float32) ** 2).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            on_chip(rows, k), on_chip(8, k, n), sizes).compile().as_text()
        calls = {name: len(re.findall(
            rf"%{name}\.\d+ [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)) for name in ("moe_gmm", "moe_tgmm")}
        assert calls == {"moe_gmm": 2, "moe_tgmm": 1}, (k, n, calls)


def test_expert_layer_takes_the_grouped_kernels_on_the_chip(
        one_chip, no_compile_cache, monkeypatch):
    """One remat'd expert layer at ``zaya1_sync_1chip_8k``'s shape (8,192
    tokens of 2,048, top-1 of 16, 8 SwiGLU experts of 2,048 held), chosen
    as on the chip: the grouped products are the kernels, under
    ``moe_experts``, and no ``ragged_dot`` is left."""
    from mpit_tpu.ops import grouped, moe

    monkeypatch.setattr(grouped, "pallas_supported", lambda: True)
    monkeypatch.setattr(grouped, "pallas_interpret", lambda: False)
    on_chip = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    params = {"router": on_chip(2048, 16), "w_gate": on_chip(8, 2048, 2048),
              "w_up": on_chip(8, 2048, 2048), "w_down": on_chip(8, 2048, 2048)}

    def loss(params, y):
        out, counters, _ = moe.moe_ffn_held(params, y, top_k=1,
                                            row_bound=8192, routing_grad=False)
        return (out.astype(jnp.float32) ** 2).sum(), counters

    text = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1),
                            has_aux=True)).lower(
        params, on_chip(8192, 2048, dt=jnp.bfloat16)).compile().as_text()
    kernels = re.findall(r"%(moe_t?gmm)\.\d+ [^\n]*op_name=\"([^\"]*)\"", text)
    assert {name for name, _ in kernels} == {"moe_gmm", "moe_tgmm"}
    for name, path in kernels:  # moe_experts/jit(_rows_call)/moe_gmm/...
        assert re.search(rf"/moe_experts/(jit\([^/]*\)/)?{name}/", path), path
    assert "ragged" not in text
