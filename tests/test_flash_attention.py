"""Flash-attention kernel ≡ dense attention (interpret mode on CPU).

The pallas kernel must compute EXACTLY softmax(QKᵀ/√d)V — same contract
ring attention proves against the same reference — across causal and
full attention, dtypes, and block/sequence-size combinations, including
the online-softmax edge cases (multi-block running max updates, fully
masked leading blocks).
"""

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops.flash_attention import flash_attention
from mpit_tpu.ops.ring_attention import dense_attention

# the module: ``mpit_tpu.ops`` re-exports the function under its name
fa = importlib.import_module("mpit_tpu.ops.flash_attention")


def _qkv(b=2, t=256, h=2, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, t, h, d)).astype(np.float32), dtype
    )
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_multiblock(self, causal):
        """T=256 with 128-blocks: two q-blocks x two k-blocks exercises
        the cross-block running-max correction and (causal) the
        skipped above-diagonal block."""
        q, k, v = _qkv()
        got = flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            use_pallas=True,
        )
        want = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_matches_dense_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16, seed=1)
        got = flash_attention(q, k, v, causal=True, use_pallas=True)
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    def test_small_blocks_many_iterations(self):
        """Tiny blocks force many online-softmax folds per row."""
        q, k, v = _qkv(t=128, seed=2)
        got = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, use_pallas=True
        )
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_untileable_length_raises_when_kernel_requested(self):
        # t=100 clamps the block to 100, which is not sublane-aligned
        # (100 % 8 != 0). A kernel that was asked for must say so — a
        # silent dense pass would let a run claim the kernel it never ran
        q, k, v = _qkv(t=100, seed=3)
        with pytest.raises(ValueError, match="T=100 does not tile"):
            flash_attention(q, k, v, causal=True, use_pallas=True)
        # unrequested (None) off-TPU stays a selection: dense, any T
        got = flash_attention(q, k, v, causal=True)
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize(
        "t,blocks,causal,dtype",
        [
            (128, 128, True, jnp.float32),   # single block
            (256, 128, True, jnp.float32),   # multi-block + skip logic
            (256, 128, False, jnp.float32),  # full attention
            (128, 32, True, jnp.float32),    # many tiny blocks
            (256, 128, True, jnp.bfloat16),  # reduced-precision inputs
        ],
    )
    def test_gradients_match_dense(self, t, blocks, causal, dtype):
        """Training through the kernel: the custom VJP (pallas dQ and
        dK/dV kernels) must produce the same q/k/v gradients as
        differentiating dense attention."""
        q, k, v = _qkv(t=t, dtype=dtype, seed=5)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2

        def loss(fn):
            return lambda q_, k_, v_: (
                fn(q_, k_, v_).astype(jnp.float32) ** 2
            ).mean()

        g_flash = jax.grad(
            loss(lambda a, b, c: flash_attention(
                a, b, c, causal=causal, block_q=blocks, block_k=blocks,
                use_pallas=True,
            )),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_dense = jax.grad(
            loss(lambda a, b, c: dense_attention(a, b, c, causal=causal)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for gf, gd in zip(g_flash, g_dense):
            np.testing.assert_allclose(
                np.asarray(gf, np.float32), np.asarray(gd, np.float32),
                rtol=tol, atol=tol,
            )

    @pytest.mark.slow
    def test_training_step_matches_xla(self):
        """One SGD step of the flash-attention model equals the xla
        model's step — the kernel is trainable, not forward-only."""
        from mpit_tpu.models.transformer import TransformerLM

        rng = np.random.default_rng(6)
        x = rng.integers(0, 31, (2, 128)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        base = TransformerLM(
            vocab_size=31, num_layers=1, d_model=32, num_heads=4,
            max_len=128, compute_dtype=jnp.float32,
        )
        params = base.init(jax.random.key(0), x)["params"]

        def step(model):
            def loss(p):
                logits = model.apply({"params": p}, x)
                logp = jax.nn.log_softmax(
                    logits.astype(jnp.float32), -1
                )
                return -jnp.take_along_axis(
                    logp, jnp.asarray(y)[..., None], -1
                ).mean()

            g = jax.grad(loss)(params)
            return jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g)

        new_xla = step(base)
        new_flash = step(base.clone(attn_impl="flash_force"))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            ),
            new_xla, new_flash,
        )

    def test_model_wiring(self):
        """TransformerLM(attn_impl='flash_force') must equal the 'xla'
        model on the same params — the flag changes scheduling, never
        math."""
        from mpit_tpu.models.transformer import TransformerLM

        x = np.random.default_rng(4).integers(0, 31, (2, 128)).astype(
            np.int32
        )
        base = TransformerLM(
            vocab_size=31, num_layers=2, d_model=32, num_heads=4,
            max_len=128, compute_dtype=jnp.float32,
        )
        params = base.init(jax.random.key(0), x)["params"]
        ref = base.apply({"params": params}, x)
        flash = base.clone(attn_impl="flash_force")
        got = flash.apply({"params": params}, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def _grads(attend, q, k, v):
    def loss(q_, k_, v_):
        return (attend(q_, k_, v_).astype(jnp.float32) ** 2).mean()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


class TestTilesFromTheShape:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("t", [1024, 512, 384, 128, 96])
    def test_chosen_tiles_fit_the_shape(self, t, d, dtype):
        """What the chip's compiler asks of a tile, and the VMEM the
        chooser promises to stay inside, for each of the three kernels."""
        chosen = fa.choose_blocks(t, d, dtype)
        assert len(chosen) == len(fa._KERNELS) == 3
        for kernel, blocks in zip(fa._KERNELS, chosen):
            for blk in blocks:
                assert t % blk == 0 and blk % 8 == 0
                assert blk % 128 == 0 or blk == t
            assert fa.vmem_estimate(
                kernel, *blocks, d, jnp.dtype(dtype).itemsize
            ) <= fa._VMEM_BUDGET < fa._VMEM_LIMIT
            # the largest such tile, not the smallest: fewer grid steps
            assert min(blocks) >= min(t, 256)

    def test_a_length_no_tile_fits_is_left_to_the_check(self):
        # 100 has no divisor that is a multiple of 128: the chooser hands
        # back tiles that span T and flash_attention refuses them (8 | T)
        assert fa.choose_blocks(100, 64, jnp.float32) == ((100, 100),) * 3

    def test_the_sweeps_choice_at_the_cells_shape(self):
        # PERF.md section 6, PR 26: one k-step forward, 512-tiles backward
        assert fa.choose_blocks(1024, 64, jnp.bfloat16) == (
            (1024, 1024), (512, 512), (512, 512))

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize(
        "dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)]
    )
    def test_chosen_tiles_match_dense(self, dtype, tol, causal):
        """T=512 with the tiles the chooser gives it, no block passed:
        forward and the three gradients. With bfloat16 inputs ``p``,
        ``dS`` and ``dO`` reach the products as bfloat16."""
        q, k, v = _qkv(b=1, t=512, dtype=dtype, seed=7)
        flash = lambda a, b, c: flash_attention(
            a, b, c, causal=causal, use_pallas=True
        )
        dense = lambda a, b, c: dense_attention(a, b, c, causal=causal)
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v), np.float32),
            np.asarray(dense(q, k, v), np.float32), rtol=tol, atol=tol,
        )
        for gf, gd in zip(_grads(flash, q, k, v), _grads(dense, q, k, v)):
            np.testing.assert_allclose(
                np.asarray(gf, np.float32), np.asarray(gd, np.float32),
                rtol=tol, atol=tol,
            )

    def test_the_cells_length_with_its_mixed_tiles(self):
        """T=1,024: the forward in one k-step, the backward kernels on
        512-tiles with one of four masked, as the cell runs them."""
        q, k, v = _qkv(b=1, t=1024, h=1, seed=10)
        flash = lambda a, b, c: flash_attention(
            a, b, c, causal=True, use_pallas=True
        )
        dense = lambda a, b, c: dense_attention(a, b, c, causal=True)
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(dense(q, k, v)),
            rtol=2e-5, atol=2e-5,
        )
        for gf, gd in zip(_grads(flash, q, k, v), _grads(dense, q, k, v)):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gd), rtol=2e-5, atol=2e-5
            )

    def test_bf16_products_keep_f32_accumulators(self):
        """Many k-blocks of bfloat16 inputs: were the running sums kept
        in bfloat16, 16 folds would lose what one fold keeps."""
        q, k, v = _qkv(b=1, t=512, dtype=jnp.bfloat16, seed=8)
        one = flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512, use_pallas=True
        )
        many = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, use_pallas=True
        )
        np.testing.assert_allclose(
            np.asarray(one, np.float32), np.asarray(many, np.float32),
            rtol=2e-2, atol=2e-2,
        )


class TestNoFetchForAMaskedTile:
    @pytest.mark.parametrize(
        "t,block_q,block_k",
        [(512, 128, 128), (512, 256, 128), (512, 128, 256), (768, 384, 128)],
    )
    def test_masked_steps_name_the_resident_block(self, t, block_q, block_k):
        """A live step names its own block; a masked step names the
        block of the live step next to it, so the pipeline sees no
        change of index and copies nothing."""
        n_q, n_k = t // block_q, t // block_k
        live = lambda i, j: j * block_k <= i * block_q + block_q - 1
        masked = 0
        for i in range(n_q):
            for j in range(n_k):
                got = int(fa._inner_k(i, j, True, block_q, block_k))
                assert int(fa._inner_k(i, j, False, block_q, block_k)) == j
                if live(i, j):
                    assert got == j
                else:  # masked steps trail the live ones of a q-block
                    masked += 1
                    assert got == int(
                        fa._inner_k(i, j - 1, True, block_q, block_k)
                    )
                    assert live(i, got)
        for j in range(n_k):
            for i in reversed(range(n_q)):
                got = int(fa._inner_q(j, i, True, block_q, block_k))
                assert int(fa._inner_q(j, i, False, block_q, block_k)) == i
                if live(i, j):
                    assert got == i
                else:  # masked steps lead the live ones of a k-block
                    masked += 1
                    assert got == int(
                        fa._inner_q(j, i + 1, True, block_q, block_k)
                    )
                    assert live(got, j)
        assert masked > 0

    def test_clamped_maps_give_the_unclamped_outputs(self, monkeypatch):
        """Six of sixteen tiles masked: outputs and gradients with the
        clamped index maps equal those with every step naming its own
        block, bit for bit (a masked step does no arithmetic)."""
        q, k, v = _qkv(b=1, t=512, seed=9)
        flash = lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=128, block_k=128, use_pallas=True
        )
        clamped = (flash(q, k, v), *_grads(flash, q, k, v))
        monkeypatch.setattr(fa, "_inner_k", lambda i, j, *rest: j)
        monkeypatch.setattr(fa, "_inner_q", lambda j, i, *rest: i)
        jax.clear_caches()  # the jitted kernels were traced with the clamp
        plain = (flash(q, k, v), *_grads(flash, q, k, v))
        jax.clear_caches()
        for a, b in zip(clamped, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(clamped[0]), np.asarray(want), rtol=2e-5, atol=2e-5
        )


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _eqns(getattr(sub, "jaxpr", sub))


def _kernel_calls(jaxpr):
    """``{scope: count}`` of the ``pallas_call``s in a jaxpr, each under
    the innermost named scope of its call site."""
    return collections.Counter(
        str(eqn.source_info.name_stack).split("/")[-1]
        for eqn in _eqns(jaxpr) if eqn.primitive.name == "pallas_call")


def _primitives(jaxpr):
    """``{primitive: count}`` over a jaxpr and every jaxpr inside it."""
    return collections.Counter(eqn.primitive.name for eqn in _eqns(jaxpr))


# a window layer and a full layer of the described block: grouped KV
# heads, rotary positions, the per-head gate, dense SwiGLU
_TWO_LAYER_ARCH = {
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "layer_types": ["sliding_attention", "full_attention"],
    "num_attention_heads_per_layer": [6, 4], "gating": "per-head",
    "rope_parameters": {"rope_theta": 10000, "partial_rotary_factor": 0.5},
}


def _two_layer_lm(block, **kw):
    from mpit_tpu.models.transformer import TransformerLM

    if block == "described":
        return TransformerLM(vocab_size=31, arch=_TWO_LAYER_ARCH,
                             compute_dtype=jnp.float32, **kw)
    return TransformerLM(vocab_size=31, max_len=32, num_layers=2, d_model=32,
                         num_heads=2, compute_dtype=jnp.float32, **kw)


class TestRematKeepsTheKernelsResiduals:
    """``TransformerLM(remat=True)`` recomputes a block on the way back,
    all but what the kernel branch names (``transformer._REMAT_KEEPS``):
    the forward kernel runs once a layer, not twice."""

    @staticmethod
    def _problem(block, **kw):
        """The model, its gradient function and parameters to take it at."""
        tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 31)
        model = _two_layer_lm(block, remat=True, **kw)
        params = jax.jit(model.clone(remat=False, attn_impl="xla").init)(
            jax.random.key(0), tokens)["params"]
        grad = lambda m: jax.grad(lambda p: jnp.square(
            m.apply({"params": p}, tokens)).mean())
        return model, grad, params

    @pytest.mark.parametrize("block,forward_scopes", [
        ("gpt2", {"flash_fwd": 2}),
        ("described", {"flash_window_fwd": 1, "flash_fwd": 1}),
    ])
    def test_forward_kernel_once_a_layer_and_the_same_gradient(
        self, block, forward_scopes
    ):
        model, grad, params = self._problem(block, attn_impl="flash_force")
        calls = _kernel_calls(jax.make_jaxpr(grad(model))(params).jaxpr)
        # two layers, three kernels each (the parent: the forward twice)
        assert sum(calls.values()) == 6, calls
        assert {s: n for s, n in calls.items()
                if s.endswith("fwd")} == forward_scopes
        want = jax.jit(grad(model.clone(remat=False)))(params)
        got = jax.jit(grad(model))(params)
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(want)[0],
            jax.tree.leaves(got),
        ):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-6, atol=1e-6,
                err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("block", ["gpt2", "described"])
    def test_dense_attention_names_nothing_and_keeps_nothing(
        self, block, monkeypatch
    ):
        """``attn_impl="xla"``: the program of a remat with no policy."""
        import flax.linen as nn

        from mpit_tpu.models import transformer

        model, grad, params = self._problem(block, attn_impl="xla")
        with_policy = _primitives(jax.make_jaxpr(grad(model))(params).jaxpr)
        assert "name" not in with_policy
        monkeypatch.setattr(
            transformer, "_RematBlock", nn.remat(transformer.Block))
        assert _primitives(
            jax.make_jaxpr(grad(model))(params).jaxpr) == with_policy
