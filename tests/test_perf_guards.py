"""Hardware-independent performance guards.

Speed is *measured* only by a chip run (PERF.md); these
tests pin COMPILED-PROGRAM properties on the CPU mesh so a perf
regression — a host round-trip in a hot loop, a lost donation, a silent
model/step change — fails the smoke tier TODAY instead of surfacing in
some future hardware session. Three guard families:

- **Analytic FLOPs pins**: the matmul/conv FLOPs/sample that
  ``bench._model_flops_per_sample`` (the MFU numerator) reports per
  preset, pinned to recorded constants. The counter is a deterministic
  host-side jaxpr walk, so any silent change to a preset's model, loss,
  or shapes moves the number and fails here — and every recorded MFU
  keeps meaning what it meant.
- **Compiled-program cleanliness + donation**: the serving decode
  segment and the fused trainer steps compile to programs with NO host
  callbacks/infeed/outfeed, and every donated buffer actually aliases
  an output (a lost donation = a full state copy per step; invisible to
  every correctness test, pure HBM/latency cost on hardware).
- **Compile-count stability**: trainer steps and serve segments reuse
  one compiled program across steps/rounds — a shape leak (recompile
  per step) would destroy throughput while still passing parity tests.
"""

import dataclasses
import pathlib
import re
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

# bench.py lives at the repo root (it is the driver's entry point, not a
# package module); make it importable regardless of pytest's invocation dir
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import bench  # noqa: E402
from mpit_tpu.parallel.common import default_loss_fn
from mpit_tpu.run import _build_model, _load_dataset
from mpit_tpu.utils.config import TrainConfig

# ------------------------------------------------------------------ helpers

FORBIDDEN_HLO = ("callback", "infeed", "outfeed")
# custom-calls are fine when they are DEVICE kernels (TopK, on TPU also
# cholesky/sort/...); what must never appear is a host-side target
_HOST_CC = re.compile(
    r'custom_call_target="[^"]*(?:callback|host|python|py_)[^"]*"',
    re.IGNORECASE,
)


def _compiled_text(jitted, *args, **kw):
    """AOT-compile and return optimized HLO text, failing on any
    donation-discard warning raised during lowering/compilation."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        txt = jitted.lower(*args, **kw).compile().as_text()
    discarded = [w for w in caught if "donat" in str(w.message).lower()]
    assert not discarded, [str(w.message) for w in discarded]
    return txt


def _assert_clean(hlo_text):
    """No host round-trips inside the compiled program: a jax.debug
    print, io/pure_callback, or infeed/outfeed added to a hot loop
    shows up as one of these regardless of backend."""
    for bad in FORBIDDEN_HLO:
        assert bad not in hlo_text, f"compiled program contains {bad!r}"
    m = _HOST_CC.search(hlo_text)
    assert m is None, f"host-side custom call in compiled program: {m.group()}"


def _alias_count(hlo_text):
    """Entries in the HLO entry module's input_output_alias map."""
    # the map is "{ {0}: (24, {}, may-alias), ... }" — the spaced braces
    # delimit the whole map (inner "{}" carries no surrounding spaces)
    m = re.search(r"input_output_alias=\{ (.*?) \}", hlo_text)
    if m is None:
        return 0
    return m.group(1).count("-alias")


# ------------------------------------------------- analytic FLOPs pins

# FLOPs/sample of jax.grad(loss) per preset — the bench's MFU numerator
# basis (dot/conv only, 2/MAC, scan bodies × trip count), computed with
# bench._jaxpr_flops on the preset's full-size model exactly as the
# hardware bench does. Recorded 2026-08-01; rel tolerance 1e-3 (the
# count is deterministic — tolerance only absorbs float accumulation).
FLOPS_PINS = {
    "mnist-easgd": 6.755226e07,  # LeNet 28px (the 67.6M calibration
    #                              constant quoted in bench.py's docs)
    "cifar-vgg-sync": 9.256612e08,  # VGG-small 32px
    "alexnet-downpour": 4.144577e09,  # AlexNet 224px
    "resnet50-sync": 2.822966e10,  # ResNet-50 224px
    "ptb-lstm-easgd": 1.687683e09,  # 2x512 LSTM, T=32
    "ptb-transformer-seq": 2.771386e09,  # 4-layer 256/1024, T=256
    "ptb-transformer-large": 1.685481e11,  # GPT-2-small shape, T=512
}


@pytest.mark.parametrize("preset", sorted(FLOPS_PINS))
def test_analytic_flops_per_sample_pinned(preset):
    """The MFU numerator per preset is pinned: a silent model/loss/shape
    change (layer count, d_model, image size, head dtype path adding or
    removing a matmul, ...) moves this count and fails here, instead of
    silently re-basing every archived MFU number."""
    cfg = TrainConfig().apply_preset(preset)
    cfg = dataclasses.replace(cfg, train_size=8)
    x, y, *_rest, meta = _load_dataset(cfg)
    model = _build_model(cfg, meta)
    if getattr(model, "seq_axis", None):
        # the bench's own convention: the dense twin computes the same
        # FLOPs per sample (bench._model_flops_per_sample)
        model = model.clone(seq_axis=None)
    loss = default_loss_fn(model.apply)
    xb, yb = jnp.asarray(x[:2]), jnp.asarray(y[:2])
    pshape = jax.eval_shape(model.init, jax.random.key(0), xb)["params"]
    jaxpr = jax.make_jaxpr(jax.grad(loss))(pshape, xb, yb)
    got = bench._jaxpr_flops(jaxpr.jaxpr) / 2
    assert got == pytest.approx(FLOPS_PINS[preset], rel=1e-3), (
        f"{preset}: analytic FLOPs/sample drifted from the recorded pin "
        f"({got:.6e} vs {FLOPS_PINS[preset]:.6e}) — if the model change "
        "is intentional, update FLOPS_PINS and note in PERF.md that "
        "earlier MFU rows predate it"
    )


# ------------------------------------- serving decode segment guards


def _serve_fixture():
    from mpit_tpu.models import Server

    from mpit_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=17, num_layers=2, d_model=32, num_heads=4, max_len=64,
        compute_dtype=jnp.float32,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params, Server(model, params, max_batch=2, segment=4)


def test_serve_segment_compiles_clean_and_donates(topo8):
    """The decode segment — the serving hot loop — contains zero host
    transfers, and BOTH donated trees (resident cache + prev tokens)
    alias outputs, so a segment updates in place with no reallocation."""
    from mpit_tpu.models import sampling, serving

    model, params, srv = _serve_fixture()
    cache = sampling._zero_cache(srv._dec, srv._nb)
    prev = jnp.zeros((srv._nb,), jnp.int32)
    keys = jnp.stack([jax.random.split(jax.random.key(0), 4)] * srv._nb)
    ones = jnp.ones((srv._nb,), jnp.float32)
    txt = _compiled_text(
        serving._serve_segment,
        srv._dec, 4, True, None, False,
        params, cache, prev, keys, ones, ones,
    )
    _assert_clean(txt)
    want = len(jax.tree.leaves(cache)) + 1  # +1: the prev-token buffer
    assert _alias_count(txt) == want, (
        "donated decode state must alias outputs leaf-for-leaf "
        f"(got {_alias_count(txt)}, want {want})"
    )


def test_serve_spec_segment_compiles_clean_and_donates(topo8):
    """The speculative segment — the spec server's hot loop — has no
    host transfers and donates all three residents (target cache,
    draft cache, prev tokens) leaf-for-leaf."""
    from mpit_tpu.models import sampling, serving
    from mpit_tpu.models.transformer import TransformerLM

    model, params, srv_unused = _serve_fixture()
    dft = TransformerLM(
        vocab_size=17, num_layers=1, d_model=16, num_heads=2, max_len=64,
        compute_dtype=jnp.float32,
    )
    dp = dft.init(jax.random.key(5), jnp.zeros((1, 8), jnp.int32))["params"]
    srv = serving.Server(model, params, max_batch=2, draft_model=dft,
                         draft_params=dp, spec_k=3, spec_rounds=2)
    nb = srv._nb
    t_cache = sampling._zero_cache(srv._dec, nb)
    d_cache = sampling._zero_cache(srv._dft, nb)
    prev = jnp.zeros((nb,), jnp.int32)
    pos0 = jnp.ones((nb,), jnp.int32)
    txt = _compiled_text(
        serving._serve_spec_segment,
        srv._dec, srv._dft, srv.spec_k, srv.spec_rounds,
        params, dp, t_cache, d_cache, prev, pos0,
        jnp.asarray(srv.spec_rounds, jnp.int32),
    )
    _assert_clean(txt)
    want = (
        len(jax.tree.leaves(t_cache)) + len(jax.tree.leaves(d_cache)) + 1
    )
    assert _alias_count(txt) == want


def test_serve_steady_state_is_one_program(topo8):
    """A drain over same-bucket requests runs ONE compiled segment
    program — retirement/admission must not leak shapes into the
    decode loop."""
    from mpit_tpu.models import serving

    model, params, srv = _serve_fixture()
    srv.submit([1, 2, 3], 9)
    srv.submit([4, 5], 9)
    srv.step()  # compiles prefill + insert + segment
    n0 = serving._serve_segment._cache_size()
    srv.submit([6, 7, 8], 9)  # admitted into the retired slots later
    srv.drain()
    assert serving._serve_segment._cache_size() == n0


def test_batch_decode_kernel_compiles_clean(topo8):
    """The batched generate kernel (_prefill_decode_scan — every
    sampling entry point's program) contains zero host transfers."""
    from mpit_tpu.models import sampling
    from mpit_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=17, num_layers=2, d_model=32, num_heads=4, max_len=64,
        compute_dtype=jnp.float32,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    dec = model.clone(decode=True, remat=False, seq_axis=None,
                      attn_impl="xla")
    nb = 4
    keys = jnp.stack([jax.random.split(jax.random.key(i), 8)
                      for i in range(nb)])
    txt = _compiled_text(
        sampling._prefill_decode_scan,
        dec, 4, 8, True, None, False, False,
        params, sampling._zero_cache(dec, nb),
        jnp.zeros((nb, 4), jnp.int32),
        jnp.ones((nb,), jnp.int32), keys,
        jnp.asarray(1.0, jnp.float32), jnp.asarray(1.0, jnp.float32),
        jnp.asarray(0.0, jnp.float32),
    )
    _assert_clean(txt)


# ------------------------------------------------ trainer step guards


def _trainer_data():
    rng = np.random.default_rng(0)
    n = 64
    x = rng.random((n, 28, 28, 1), np.float32)
    y = rng.integers(0, 10, (n,))
    return x, y


def test_easgd_round_compiles_clean_and_donates(topo8):
    """The fused τ-round (τ local steps + elastic exchange as one
    program) has no host transfers and donates its whole state tree —
    worker params, worker opt, center, counter — leaf-for-leaf."""
    from mpit_tpu.models import MLP
    from mpit_tpu.parallel import EASGDTrainer

    tr = EASGDTrainer(
        MLP(compute_dtype=jnp.float32),
        optax.sgd(0.05, momentum=0.9), topo8, tau=2,
    )
    x, y = _trainer_data()
    state = tr.init_state(jax.random.key(0), x[:2])
    xr, yr = tr.round_batches(
        x.reshape(2, 32, 28, 28, 1), y.reshape(2, 32)
    )
    txt = _compiled_text(tr._round, state, xr, yr)
    _assert_clean(txt)
    want = len(jax.tree.leaves(state))
    assert _alias_count(txt) == want, (
        f"donated trainer state must alias leaf-for-leaf "
        f"(got {_alias_count(txt)}, want {want})"
    )
    # compile-count stability: rounds 2..N reuse round 1's program
    state, _ = tr.step(state, x.reshape(2, 32, 28, 28, 1), y.reshape(2, 32))
    n0 = tr._round._cache_size()
    for i in (1, 2):
        xi = np.roll(x, i, axis=0)
        state, _ = tr.step(
            state, xi.reshape(2, 32, 28, 28, 1), y.reshape(2, 32)
        )
    assert tr._round._cache_size() == n0 == 1


def test_seq_parallel_step_compiles_clean_and_donates():
    """Same guards for the seq-parallel trainer — the step both flagship
    MFU presets (ptb-transformer-seq/-large) actually run."""
    import mpit_tpu
    from mpit_tpu.models.transformer import TransformerLM
    from mpit_tpu.parallel import SeqParallelTrainer

    mpit_tpu.finalize()
    topo = mpit_tpu.init(axis_names=("dp", "sp"), mesh_shape=(4, 2))
    model = TransformerLM(
        vocab_size=31, num_layers=2, d_model=32, num_heads=2, max_len=64,
        compute_dtype=jnp.float32, seq_axis="sp",
    )
    tr = SeqParallelTrainer(model, optax.sgd(0.1, momentum=0.9), topo)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 31, (8, 64)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    state = tr.init_state(jax.random.key(0), x[:2, :32])
    txt = _compiled_text(tr._step, state, x, y)
    _assert_clean(txt)
    want = len(jax.tree.leaves(state))
    assert _alias_count(txt) == want
    state, _ = tr.step(state, x, y)
    n0 = tr._step._cache_size()
    state, _ = tr.step(state, np.roll(x, 1, axis=0), y)
    assert tr._step._cache_size() == n0 == 1


def test_downpour_round_compiles_clean_and_donates(topo8):
    """Same guards for the Downpour τ-round."""
    from mpit_tpu.models import MLP
    from mpit_tpu.parallel import DownpourTrainer

    tr = DownpourTrainer(
        MLP(compute_dtype=jnp.float32),
        optax.sgd(0.05, momentum=0.9), topo8, tau=2,
    )
    x, y = _trainer_data()
    state = tr.init_state(jax.random.key(0), x[:2])
    xr, yr = tr.round_batches(
        x.reshape(2, 32, 28, 28, 1), y.reshape(2, 32)
    )
    txt = _compiled_text(tr._round, state, xr, yr)
    _assert_clean(txt)
    assert _alias_count(txt) == len(jax.tree.leaves(state))


def test_zero_step_compiles_clean_and_donates(topo8):
    """Same guards for ZeRO-1 (sharded Adam state; reduce-scatter +
    all-gather inside the step)."""
    from mpit_tpu.models import MLP
    from mpit_tpu.parallel import ZeroDataParallelTrainer

    tr = ZeroDataParallelTrainer(
        MLP(compute_dtype=jnp.float32), optax.adam(1e-3), topo8,
    )
    x, y = _trainer_data()
    state = tr.init_state(jax.random.key(0), x[:2])
    txt = _compiled_text(tr._step, state, x[:32], y[:32])
    _assert_clean(txt)
    assert _alias_count(txt) == len(jax.tree.leaves(state))


def test_moe_step_compiles_clean_and_donates(topo8):
    """Same guards for the expert-parallel step (all_to_all dispatch
    compiles into the program; no host hops around it)."""
    from mpit_tpu.models.transformer import TransformerLM
    from mpit_tpu.parallel import MoEParallelTrainer

    model = TransformerLM(
        vocab_size=31, num_layers=2, d_model=32, num_heads=4, max_len=16,
        compute_dtype=jnp.float32, moe_experts=8,
        moe_axis=topo8.worker_axis, moe_capacity_factor=4.0,
    )
    tr = MoEParallelTrainer(model, optax.sgd(0.1, momentum=0.9), topo8)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 31, (8, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    state = tr.init_state(jax.random.key(0), x[:2])
    if tr._step is None:
        tr._build(state)  # the lazy builder step() itself would call
    txt = _compiled_text(tr._step, state, jnp.asarray(x), jnp.asarray(y))
    _assert_clean(txt)
    assert _alias_count(txt) == len(jax.tree.leaves(state))


def test_composed_step_compiles_clean_and_donates():
    """Same guards for the 3-D dp×tp×sp composed step."""
    import mpit_tpu
    from mpit_tpu.models.transformer import TransformerLM
    from mpit_tpu.parallel import ComposedParallelTrainer

    mpit_tpu.finalize()
    topo = mpit_tpu.init(
        axis_names=("dp", "tp", "sp"), mesh_shape=(2, 2, 2)
    )
    model = TransformerLM(
        vocab_size=29, num_layers=2, d_model=32, num_heads=8, max_len=32,
        compute_dtype=jnp.float32, seq_axis="sp",
    )
    rng = np.random.default_rng(0)
    x = rng.integers(0, 29, (8, 32)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    try:
        tr = ComposedParallelTrainer(
            model, optax.sgd(0.1, momentum=0.9), topo
        )
        state = tr.init_state(jax.random.key(0), x[:2, :16])
        txt = _compiled_text(
            tr._step, state, jnp.asarray(x), jnp.asarray(y)
        )
    except Exception as e:  # old jaxlibs can't SPMD-partition the
        if "PartitionId instruction is not supported" in str(e):
            pytest.skip(  # partial-manual (axis_names=) shard_map mode
                "backend cannot compile partial-manual shard_map"
            )
        raise
    _assert_clean(txt)
    assert _alias_count(txt) == len(jax.tree.leaves(state))


def test_pipeline_step_compiles_clean_and_donates():
    """Same guards for the pipeline trainer (gpipe default): its
    stage-sharded state dict (params + momentum + step) must donate
    leaf-for-leaf — this trainer historically lacked donation, which a
    correctness suite can never notice."""
    import mpit_tpu
    from mpit_tpu.parallel import PipelineParallelTrainer

    mpit_tpu.finalize()
    topo = mpit_tpu.init(axis_names=("dp", "pp"), mesh_shape=(2, 4))
    tr = PipelineParallelTrainer(
        vocab_size=31, num_layers=4, d_model=32, num_heads=2,
        seq_len=32, topo=topo, n_micro=2,
    )
    state = tr.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    x = rng.integers(0, 31, (8, 32)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    txt = _compiled_text(tr._step, state, jnp.asarray(x), jnp.asarray(y))
    _assert_clean(txt)
    want = len(jax.tree.leaves(state))
    assert _alias_count(txt) == want
    state, _ = tr.step(state, x, y)
    n0 = tr._step._cache_size()
    state, _ = tr.step(state, np.roll(x, 1, axis=0), y)
    assert tr._step._cache_size() == n0 == 1


def test_sync_serial_fallback_bit_identical(topo8):
    """The trainer's one step program is the fused step EXACTLY: params
    equal to the BIT after several fixed-seed steps against a verbatim
    reimplementation of it (value_and_grad, one pmean, the optimizer)."""
    from jax.sharding import PartitionSpec as P

    from mpit_tpu.models import MLP
    from mpit_tpu.parallel import DataParallelTrainer
    from mpit_tpu.parallel import common as pcommon

    model = MLP(compute_dtype=jnp.float32)
    opt = optax.sgd(0.05, momentum=0.9)
    tr = DataParallelTrainer(model, opt, topo8, donate_state=False)
    x, y = _trainer_data()
    state = tr.init_state(jax.random.key(0), x[:2])

    axis = topo8.worker_axis
    loss_fn = pcommon.default_loss_fn(model.apply)

    # the step, verbatim
    def train_step(state, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, xb, yb)
        grads = jax.lax.pmean(grads, axis)
        loss = jax.lax.pmean(loss, axis)
        updates, opt_state = opt.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return (
            pcommon.TrainState(
                params=params, opt_state=opt_state, step=state.step + 1
            ),
            {"loss": loss},
        )

    ref_step = jax.jit(
        jax.shard_map(
            train_step,
            mesh=topo8.mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    s_tr, s_ref = state, state
    for i in range(3):
        xb = np.roll(x, i, axis=0)[:32]
        yb = np.roll(y, i, axis=0)[:32]
        s_tr, _ = tr.step(s_tr, xb, yb)
        s_ref, m_ref = ref_step(s_ref, xb, yb)
        jax.block_until_ready(m_ref)
    for a, b in zip(
        jax.tree.leaves(s_tr.params), jax.tree.leaves(s_ref.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sync_step_compiles_clean_and_donates(topo8):
    """Same three guards for the sync-DP fused step (pmean inside the
    jitted program, donated TrainState)."""
    from mpit_tpu.models import MLP
    from mpit_tpu.parallel import DataParallelTrainer

    tr = DataParallelTrainer(
        MLP(compute_dtype=jnp.float32),
        optax.sgd(0.05, momentum=0.9), topo8,
    )
    x, y = _trainer_data()
    state = tr.init_state(jax.random.key(0), x[:2])
    txt = _compiled_text(tr._step, state, x[:32], y[:32])
    _assert_clean(txt)
    want = len(jax.tree.leaves(state))
    assert _alias_count(txt) == want
    state, _ = tr.step(state, x[:32], y[:32])
    n0 = tr._step._cache_size()
    state, _ = tr.step(state, x[32:], y[32:])
    assert tr._step._cache_size() == n0 == 1
