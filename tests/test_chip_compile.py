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
