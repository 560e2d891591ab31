"""Flash-attention kernel ≡ dense attention (interpret mode on CPU).

The pallas kernel must compute EXACTLY softmax(QKᵀ/√d)V — same contract
ring attention proves against the same reference — across causal and
full attention, dtypes, and block/sequence-size combinations, including
the online-softmax edge cases (multi-block running max updates, fully
masked leading blocks).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops.flash_attention import flash_attention
from mpit_tpu.ops.ring_attention import dense_attention


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
        got = flash_attention(q, k, v, causal=causal, use_pallas=True)
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
