"""The program's own names for its work: ``utils/profiling.span`` on the
host (a registry, and events in a profiler trace), the spans the two fit
loops and ``init_state`` emit, and the ``jax.named_scope`` of the unit
programs, which must be metadata only. CPU."""

import glob
import re

import jax
import numpy as np
import optax
import pytest

from mpit_tpu.data import Batches
from mpit_tpu.models import MLP
from mpit_tpu.models.transformer import TransformerLM
from mpit_tpu.parallel import DataParallelTrainer, EASGDTrainer
from mpit_tpu.utils import profiling
from mpit_tpu.utils.profiling import span

FIT_SPANS = ("mpit.fit.group", "mpit.fit.stage", "mpit.fit.dispatch",
             "mpit.fit.callback")


@pytest.fixture(autouse=True)
def _empty_registry():
    profiling.reset()
    yield
    profiling.reset()


def _images(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.uniform(0, 1, (n, 8, 8, 1)).astype(np.float32),
            rng.integers(0, 10, (n,)).astype(np.int32))


def _tokens(n=32, t=16, vocab=61, seed=0):
    x = np.random.default_rng(seed).integers(0, vocab, (n, t)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def _tiny_lm(vocab=61):
    return TransformerLM(vocab_size=vocab, num_layers=2, d_model=32,
                         num_heads=4, max_len=16)


def _trainer(kind, topo, model=None):
    model = model or MLP(hidden=(16,), num_classes=10)
    if kind == "easgd":
        return EASGDTrainer(model, optax.sgd(0.1), topo, tau=2), "on_round", 2
    return DataParallelTrainer(model, optax.sgd(0.1), topo), "on_step", 1


def test_span_records_count_total_max_and_ring():
    for _ in range(3):
        with span("t.work", unit=1):
            pass
    with span("t.other"):
        pass
    snap = profiling.snapshot()
    work = snap["t.work"]
    assert work["count"] == 3 and len(work["last_s"]) == 3
    assert work["total_s"] == pytest.approx(sum(work["last_s"]))
    assert work["max_s"] == max(work["last_s"]) > 0
    assert snap["t.other"]["count"] == 1
    profiling.reset()
    assert profiling.snapshot() == {}


def test_the_ring_keeps_the_newest_durations_and_the_counts_keep_all():
    for _ in range(profiling.RING + 10):
        with span("t.many"):
            pass
    many = profiling.snapshot()["t.many"]
    assert many["count"] == profiling.RING + 10
    assert len(many["last_s"]) == profiling.RING
    assert many["total_s"] >= sum(many["last_s"])


def test_spans_nest_and_survive_an_exception():
    with pytest.raises(KeyError):
        with span("t.outer"):
            with span("t.inner"):
                raise KeyError("x")
    snap = profiling.snapshot()
    assert snap["t.outer"]["count"] == snap["t.inner"]["count"] == 1
    assert snap["t.outer"]["total_s"] >= snap["t.inner"]["total_s"]


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: the order in which
    spans open, with their ids."""

    log = []

    def __init__(self, name, **ids):
        self.entry = (name, ids.get("unit"))

    def __enter__(self):
        self.log.append(self.entry)

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("kind", ["easgd", "sync"])
def test_fit_emits_each_span_once_a_unit_and_stages_ahead(kind, topo8,
                                                          monkeypatch):
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    trainer, hook, tau = _trainer(kind, topo8)
    x, y = _images(n=16 * 6 * tau)
    state = trainer.init_state(jax.random.key(0), x[:2])
    seen = []
    trainer.fit(Batches(x, y, global_batch=16), state, epochs=1, prefetch=2,
                **{hook: lambda done, st, m: seen.append(done)})
    units = 6
    assert seen == list(range(1, units + 1))
    log = [e for e in _Recorder.log if e[0] in FIT_SPANS]
    for name in FIT_SPANS:
        assert [u for n, u in log if n == name] == list(range(1, units + 1))
    for k in range(1, units + 1):
        before = log[:log.index(("mpit.fit.dispatch", k))]
        staged = [u for n, u in before if n == "mpit.fit.stage"]
        # prefetch=2: units k+1 and k+2 are on their way when k is dispatched
        assert staged == list(range(1, min(k + 2, units) + 1))
        assert ("mpit.fit.group", k) in before
        assert log.index(("mpit.fit.callback", k)) > len(before)
    snap = profiling.snapshot()
    assert snap["mpit.input.batch"]["count"] == units * tau
    assert snap["mpit.setup.init_state"]["count"] == 1
    for name in FIT_SPANS:
        assert snap[name]["count"] == units


def test_a_profiler_trace_holds_the_dispatch_span_with_its_unit(topo8, tmp_path):
    from jax.profiler import ProfileData

    trainer, hook, tau = _trainer("easgd", topo8)
    x, y = _images(n=16 * 3 * tau)
    state = trainer.init_state(jax.random.key(0), x[:2])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        state, _ = trainer.fit(Batches(x, y, global_batch=16), state, epochs=1)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    units = sorted(
        dict(ev.stats)["unit"]
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name == "mpit.fit.dispatch")
    assert units == [1, 2, 3]


def _lm_round(topo):
    trainer = EASGDTrainer(_tiny_lm(), optax.adamw(1e-3), topo, tau=2,
                           donate_state=False)
    x, y = _tokens()
    state = trainer.init_state(jax.random.key(0), x[:2])
    xr, yr = trainer.round_batches(np.stack([x[:16], x[16:]]),
                                   np.stack([y[:16], y[16:]]))
    return trainer, state, xr, yr


def _scopes(text):
    """The named-scope components of every ``op_name`` in a compiled text,
    unwrapped from the transformations jax names them through."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for part in op_name.split("/"):
            found.add(re.sub(r"^(?:\w+\()*([\w.\-]+)\)*$", r"\1", part))
    return found


def test_the_compiled_round_holds_an_instruction_under_every_scope(topo8):
    trainer, state, xr, yr = _lm_round(topo8)
    text = trainer._round.lower(state, xr, yr).compile().as_text()
    assert {"attention", "attn_proj", "mlp", "head", "loss", "optimizer",
            "elastic"} <= _scopes(text)
    # one name covers forward and backward
    assert re.search(r'op_name="[^"]*/jvp\(TransformerLM\)/Block_0/attention/', text)
    assert re.search(
        r'op_name="[^"]*/transpose\(jvp\(TransformerLM\)\)/Block_0/attention/', text)


def test_the_sync_step_names_its_exchange_and_its_optimizer(topo8):
    trainer, _, _ = _trainer("sync", topo8, model=_tiny_lm())
    x, y = _tokens()
    state = trainer.init_state(jax.random.key(0), x[:2])
    text = trainer._step.lower(state, x[:16], y[:16]).compile().as_text()
    assert {"grad_exchange", "optimizer", "loss", "attention", "mlp",
            "head"} <= _scopes(text)


def test_named_scopes_are_metadata_only(topo8, monkeypatch):
    import contextlib

    trainer, state, xr, yr = _lm_round(topo8)
    named_state, named = trainer._round(state, xr, yr)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    trainer, state, xr, yr = _lm_round(topo8)
    text = trainer._round.lower(state, xr, yr).compile().as_text()
    assert "attention" not in _scopes(text)  # the patch reached the program
    plain_state, plain = trainer._round(state, xr, yr)
    assert np.array_equal(np.asarray(named["loss"]), np.asarray(plain["loss"]))
    for a, b in zip(jax.tree.leaves(named_state), jax.tree.leaves(plain_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_unit_program_text_is_the_text_of_the_unit_fit_ran(topo8):
    assert profiling.unit_program_text() is None  # no fit loop ran yet
    trainer, hook, tau = _trainer("easgd", topo8)
    x, y = _images(n=16 * 2 * tau)
    state = trainer.init_state(jax.random.key(0), x[:2])
    trainer.fit(Batches(x, y, global_batch=16), state, epochs=1)
    text = profiling.unit_program_text()
    assert text.startswith("HloModule jit_round_step")
    assert {"loss", "optimizer", "elastic"} <= _scopes(text)


def test_run_returns_the_spans_without_their_rings():
    from mpit_tpu.run import run
    from mpit_tpu.utils import TrainConfig

    cfg = TrainConfig(model="mlp", dataset="mnist", algo="easgd", tau=2,
                      global_batch=64, epochs=1, train_size=512)
    spans = run(cfg)["spans"]
    rounds = 512 // 64 // 2
    assert spans["mpit.fit.dispatch"]["count"] == rounds
    assert spans["mpit.fit.callback"]["count"] == rounds
    assert spans["mpit.setup.init_state"]["count"] == 1
    assert set(spans["mpit.fit.dispatch"]) == {"count", "total_s", "max_s"}


def test_unit_program_text_names_this_source_past_a_stale_cache(tmp_path):
    """The persistent cache's key leaves metadata out: a program cached under
    other scopes is handed back with those names. The text must not be."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def unit(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.sin(x) @ x
        return jax.jit(step)

    flags = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in flags}
    for k, v in flags.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        # committed, as a fit loop's staged arguments are: the call below
        # and ``unit_program_text`` then share one lowering, whose
        # ``compile()`` is memoized
        x = jax.device_put(jnp.ones((64, 64)), jax.devices()[0])
        unit("older")(x).block_until_ready()  # fills the cache
        newer = unit("newer")
        newer(x).block_until_ready()
        stale = newer.lower(x).compile().as_text()
        assert "older" in _scopes(stale) and "newer" not in _scopes(stale)
        profiling.remember_unit(newer, x)
        fresh = _scopes(profiling.unit_program_text())
        assert "newer" in fresh and "older" not in fresh
        assert jax.config.jax_enable_compilation_cache  # switched back on
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
