"""Synchronous allreduce data parallelism.

Reference parity (SURVEY.md §3(d), BASELINE.json:8): per step each worker
computes a gradient on its batch shard, ``mpiT.Allreduce(grad, SUM)`` then
``grad /= size``, and a replicated optimizer applies the averaged gradient.

TPU-native design: one jit-compiled ``shard_map`` step over the worker mesh
axis — the batch is sharded on the leading axis, params/optimizer state are
replicated, and the gradient average is a single ``lax.pmean`` that XLA lowers
to an ICI all-reduce fused into the step (no host round trip per step, unlike
the reference's per-step MPI call from the Lua loop).

Bucketed / quantized gradient exchange: when ``MPIT_DP_QUANT`` or ``MPIT_DP_BUCKET_BYTES`` engages it,
the step is restructured into a program pipeline — one backward program
that emits the gradient as size-targeted flat *buckets*, then per bucket a
staged reduce-scatter + all-gather exchange whose wire hops are separate
XLA programs from the (optional) quantize/dequantize math, and one apply
program that rebuilds the gradient tree and runs the optimizer. Separate
hop programs are what buys both halves of the ROADMAP fast-wire item:

- **overlap** — on a real accelerator the host dispatches every program
  asynchronously, so bucket k's all_to_all is in flight while bucket k+1's
  encode (and the next bucket's math) runs — double-buffering at program
  granularity without splitting the backward itself;
- **honest attribution** — when obs is armed each hop is timed and
  journaled as a ``send`` event while the quant math blocks inside
  ``compute`` spans, so ``obs roofline`` shows the wire *shrinking* under
  quantization rather than hiding quant compute inside the wire figure.

The quantized exchange (``comm.collectives.quantized_allreduce`` math, run
here as staged programs) carries two-level error-feedback residuals in
trainer state — level 1 on each worker's contribution, level 2 on its
owned reduced chunk — so the accumulated gradient stream stays unbiased
(docs/WIRE.md "Quantized collectives").

With both knobs off the trainer builds and runs EXACTLY the fused
single-program step above — bit-identical to the pre-bucketing trainer,
pinned by tests/test_perf_guards.py.
"""

from __future__ import annotations

import os
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from mpit_tpu.analysis import runtime as _runtime

from mpit_tpu import quant as _quant
from mpit_tpu.comm.topology import topology as _current_topology
from mpit_tpu.comm.topology import Topology
from mpit_tpu.obs import core as obs_core
from mpit_tpu.parallel import common
from mpit_tpu.utils.profiling import span

# bucket size target when bucketing is engaged without an explicit size:
# big enough that hop dispatch overhead amortizes, small enough that a
# ResNet-scale gradient still splits into several overlappable buckets
DEFAULT_DP_BUCKET_BYTES = 4 << 20


def dp_quant_from_env(env=None) -> str:
    """``MPIT_DP_QUANT`` (off|bf16|int8; default off) — the sync-DP
    gradient-exchange quantization mode."""
    env = os.environ if env is None else env
    mode = env.get("MPIT_DP_QUANT") or "off"
    if mode not in _quant.QUANT_MODES:
        raise ValueError(
            f"MPIT_DP_QUANT={mode!r}: expected one of {_quant.QUANT_MODES}"
        )
    return mode


def dp_bucket_bytes_from_env(env=None) -> Optional[int]:
    """``MPIT_DP_BUCKET_BYTES`` (positive int, f32 bytes per bucket) —
    setting it engages the bucketed exchange even unquantized. None when
    unset."""
    env = os.environ if env is None else env
    raw = env.get("MPIT_DP_BUCKET_BYTES")
    if raw is None or raw == "":
        return None
    b = int(raw)
    if b < 1:
        raise ValueError(f"MPIT_DP_BUCKET_BYTES={b} must be >= 1")
    return b


class _Bucket:
    """One gradient bucket: leaves ``[lo, hi)`` concatenated to a flat
    f32 vector of ``n`` elements, padded to ``n_pad`` (W-divisible; each
    worker owns a ``chunk``-element row of the reduce-scatter)."""

    __slots__ = ("lo", "hi", "n", "n_pad", "chunk", "hop_bytes")

    def __init__(self, lo: int, hi: int, n: int, w: int, mode: str):
        self.lo, self.hi, self.n = lo, hi, n
        self.n_pad = n + (-n % w)
        self.chunk = self.n_pad // w
        # per-worker wire volume of ONE hop (all_to_all out or all_gather
        # in are both the full padded bucket at wire width; int8 adds W
        # block scales)
        self.hop_bytes = self.n_pad * _quant.MODE_ITEMSIZE[mode] + (
            4 * w if mode == "int8" else 0
        )


class _BucketPlan:
    """Leaf layout + bucket partition for one parameter structure.

    Buckets are contiguous runs of flatten-order leaves closed once the
    accumulated f32 bytes reach the target (leaves are never split — a
    leaf larger than the target becomes its own bucket)."""

    def __init__(self, params, w: int, bucket_bytes: int, mode: str):
        leaves, self.treedef = jax.tree.flatten(params)
        self.shapes = [jnp.shape(l) for l in leaves]
        self.dtypes = [jnp.asarray(l).dtype for l in leaves]
        self.sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        self.buckets: List[_Bucket] = []
        lo, acc = 0, 0
        for i, sz in enumerate(self.sizes):
            acc += sz * 4
            if acc >= bucket_bytes:
                self.buckets.append(
                    _Bucket(lo, i + 1, sum(self.sizes[lo : i + 1]), w, mode)
                )
                lo, acc = i + 1, 0
        if lo < len(self.sizes):
            self.buckets.append(
                _Bucket(lo, len(self.sizes), sum(self.sizes[lo:]), w, mode)
            )

    def wire_bytes_per_step(self) -> int:
        """Per-worker bytes the exchange puts on the wire each step (two
        hops per bucket) — the bench.py A/B instrument."""
        return sum(2 * b.hop_bytes for b in self.buckets)


class DataParallelTrainer:
    """Sync allreduce DP trainer for a flax model.

    Usage::

        topo = mpit_tpu.init()
        trainer = DataParallelTrainer(model, optax.sgd(0.1), topo)
        state = trainer.init_state(jax.random.key(0), sample_batch_x)
        state, metrics = trainer.step(state, x_global, y_global)

    ``quant``/``bucket_bytes`` (default: the ``MPIT_DP_QUANT`` /
    ``MPIT_DP_BUCKET_BYTES`` knobs) select the bucketed exchange — see
    the module docstring. With both off the step is the fused
    single-program path, bit-identical to the pre-bucketing trainer.
    ``obs`` (default: :func:`mpit_tpu.obs.core.config_from_env`) arms
    per-step roofline + dynamics journaling on the bucketed path; call
    :meth:`close_obs` to flush the journal before reading it.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        topo: Optional[Topology] = None,
        loss_fn: Optional[Callable] = None,
        donate_state: bool = True,
        accum_steps: int = 1,
        quant: Optional[str] = None,
        bucket_bytes: Optional[int] = None,
        obs: Optional[obs_core.ObsConfig] = None,
        jit_init: bool = False,
    ):
        """``jit_init``: make the state in one jitted program, born
        replicated, instead of op by op and then placed: for a model
        whose eager ``init`` is a forward pass too large to run that way.

        ``accum_steps``: gradient accumulation — each step's local
        batch is processed as that many sequential slices (``lax.scan``)
        whose gradients average before the one optimizer update. The
        math is EXACTLY the full-batch step (equal slice sizes, mean
        losses, and no model here carries batch statistics — GroupNorm/
        LayerNorm only), so it trades step latency for peak activation
        memory: effective batch B needs only B/accum_steps of forward
        state in HBM at once."""
        self.model = model
        self.optimizer = optimizer
        self.topo = topo if topo is not None else _current_topology()
        self.loss_fn = (
            loss_fn
            if loss_fn is not None
            else common.default_loss_fn(model.apply)
        )
        self.accum_steps = accum = int(accum_steps)
        self.donate_state = donate_state
        self.jit_init = jit_init
        self.quant = dp_quant_from_env() if quant is None else quant
        if self.quant not in _quant.QUANT_MODES:
            raise ValueError(
                f"quant={self.quant!r}: expected one of {_quant.QUANT_MODES}"
            )
        bb = (
            bucket_bytes
            if bucket_bytes is not None
            else dp_bucket_bytes_from_env()
        )
        self.bucketed = self.quant != "off" or bb is not None
        self.bucket_bytes = (
            int(bb) if bb is not None else DEFAULT_DP_BUCKET_BYTES
        )
        if self.bucket_bytes < 1:
            raise ValueError(
                f"bucket_bytes={self.bucket_bytes} must be >= 1"
            )
        self.obs = obs if obs is not None else obs_core.config_from_env()
        self._tracer: Optional[obs_core.Tracer] = None
        self._round = 0
        # bucketed-path machinery is shape-dependent; built on first step
        self._plan: Optional[_BucketPlan] = None

        axis = self.topo.worker_axis
        mesh = self.topo.mesh
        # a model may offer (params, x, y) -> (loss, counters) beside its
        # apply (expert layers' routing counts): the step's metrics then
        # carry the counters
        counting = (
            getattr(model, "loss_with_counters", None)
            if loss_fn is None and not self.bucketed else None
        )
        local_vg = common.accumulated_value_and_grad(
            counting or self.loss_fn, accum, has_aux=counting is not None
        )
        self._local_vg = local_vg

        def train_step(state: common.TrainState, x, y):
            loss, grads = local_vg(state.params, x, y)
            counters = {}
            if counting is not None:
                loss, counters = loss
            # the one collective of the step: grad average over workers
            with jax.named_scope("grad_exchange"):
                grads = jax.lax.pmean(grads, axis)
                loss = jax.lax.pmean(loss, axis)
                counters = jax.lax.pmean(counters, axis)
            with jax.named_scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
            return (
                common.TrainState(
                    params=params, opt_state=opt_state, step=state.step + 1
                ),
                {"loss": loss, **counters},
            )

        self._step = jax.jit(
            jax.shard_map(
                train_step,
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis)),
                out_specs=(P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0,) if donate_state else (),
        )

        self._eval = common.build_count_loss_eval(model, self.topo)

    def init_state(self, rng, sample_x) -> common.TrainState:
        """Initialize replicated state. ``sample_x`` is a *per-worker* shaped
        batch (leading dim = per-worker batch); only shapes matter."""
        with span("mpit.setup.init_state"):
            create = lambda key, x: common.TrainState.create(
                self.model.init(key, x)["params"], self.optimizer
            )
            if self.jit_init:
                return jax.block_until_ready(jax.jit(
                    create, out_shardings=self.topo.replicated_sharding()
                )(rng, jnp.asarray(sample_x)))
            state = create(rng, jnp.asarray(sample_x))
            # waited for, so that the span reads set-up done, not dispatched
            return jax.block_until_ready(
                jax.device_put(state, self.topo.replicated_sharding())
            )

    def _check(self, x) -> None:
        common.check_accum_batch(
            len(x), self.topo.num_workers, self.accum_steps
        )

    # -- bucketed exchange machinery ------------------------------------

    def _ensure_buckets(self, params) -> None:
        if self._plan is not None:
            return
        w = self.topo.num_workers
        axis = self.topo.worker_axis
        mesh = self.topo.mesh
        mode = self.quant
        plan = _BucketPlan(params, w, self.bucket_bytes, mode)
        self._plan = plan
        nb = len(plan.buckets)
        local_vg = self._local_vg

        def _sm(fn, in_specs, out_specs, donate=()):
            return jax.jit(
                jax.shard_map(
                    fn,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=out_specs,
                    check_vma=False,
                ),
                donate_argnums=donate,
            )

        # program 1 — backward: local grads flattened into padded buckets
        # (pmean'd loss is the program's one collective; gradients leave
        # UNREDUCED, one (W, n_pad) row-block per bucket)
        def grads_step(params, x, y):
            loss, grads = local_vg(params, x, y)
            loss = lax.pmean(loss, axis)
            leaves = jax.tree.flatten(grads)[0]
            outs = [loss]
            for b in plan.buckets:
                parts = [
                    leaves[i].reshape(-1).astype(jnp.float32)
                    for i in range(b.lo, b.hi)
                ]
                flat = (
                    jnp.concatenate(parts) if len(parts) > 1 else parts[0]
                )
                if b.n_pad > b.n:
                    flat = jnp.pad(flat, (0, b.n_pad - b.n))
                outs.append(flat[None])
            return tuple(outs)

        self._grads_p = _sm(
            grads_step,
            (P(), P(axis), P(axis)),
            (P(), *[P(axis, None)] * nb),
        )

        if mode != "off":
            # program 2 — encode (math only, no collectives): level-1 EF
            # fold, blockwise quantize, new residual + its local sumsq
            def encode(row, r):
                c = row[0] + r[0]
                rows = c.reshape(w, -1)
                codes, scales = _quant.quantize_rows_jnp(rows, mode)
                deq = _quant.dequantize_rows_jnp(codes, scales, mode)
                new_r = c - deq.reshape(-1)
                return (
                    codes.reshape(1, -1),
                    scales.reshape(1, -1),
                    new_r[None],
                    jnp.sum(new_r * new_r)[None],
                )

            self._encode_p = _sm(
                encode,
                (P(axis, None), P(axis, None)),
                (P(axis, None), P(axis, None), P(axis, None), P(axis)),
                donate=(1,),
            )

            # program 3 — wire hop 1: the all_to_all of codes (+ scales
            # for int8; bf16 is scale-free). COLLECTIVE-ONLY by design:
            # its wall time is the journaled wire figure.
            def hop1(codes, scales):
                cx = lax.all_to_all(
                    codes[0].reshape(w, -1),
                    axis,
                    split_axis=0,
                    concat_axis=0,
                )
                if mode == "int8":
                    sx = lax.all_to_all(
                        scales.reshape(w, 1),
                        axis,
                        split_axis=0,
                        concat_axis=0,
                    ).reshape(1, -1)
                else:
                    sx = scales
                return cx.reshape(1, -1), sx

            self._hop1_p = _sm(
                hop1,
                (P(axis, None), P(axis, None)),
                (P(axis, None), P(axis, None)),
            )

            # program 4 — reduce (math only): dequantize received rows,
            # f32 mean, level-2 EF fold, requantize the owned chunk
            def reduce_q(cx, sx, r2):
                rows = _quant.dequantize_rows_jnp(
                    cx[0].reshape(w, -1), sx.reshape(w, 1), mode
                )
                red = jnp.sum(rows, axis=0) / w + r2[0]
                rcodes, rscale = _quant.quantize_jnp(red, mode)
                new_r2 = red - _quant.dequantize_jnp(rcodes, rscale, mode)
                return rcodes[None], rscale[None], new_r2[None]

            self._reduce_p = _sm(
                reduce_q,
                (P(axis, None), P(axis, None), P(axis, None)),
                (P(axis, None), P(axis), P(axis, None)),
                donate=(2,),
            )

            # program 5 — wire hop 2: all_gather of reduced codes
            def hop2(rcodes, rscale):
                g = lax.all_gather(rcodes[0], axis)
                if mode == "int8":
                    gs = lax.all_gather(rscale[0], axis)
                else:
                    gs = jnp.ones((w,), jnp.float32)
                return g, gs

            self._hop2_p = _sm(
                hop2, (P(axis, None), P(axis)), (P(), P())
            )

            # two-level EF residual state (module docstring / docs/WIRE.md)
            shard = self.topo.worker_sharding()
            self._residual = [
                jax.device_put(np.zeros((w, b.n_pad), np.float32), shard)
                for b in plan.buckets
            ]
            self._residual2 = [
                jax.device_put(np.zeros((w, b.chunk), np.float32), shard)
                for b in plan.buckets
            ]
        else:
            # raw buckets: same staged reduce-scatter + all-gather wire
            # pattern at full f32 width (the A/B baseline the quantized
            # path is measured against)
            def hop1_raw(row):
                return lax.all_to_all(
                    row[0].reshape(w, -1), axis, split_axis=0, concat_axis=0
                ).reshape(1, -1)

            def reduce_raw(xch):
                return (jnp.sum(xch[0].reshape(w, -1), axis=0) / w)[None]

            def hop2_raw(red):
                return lax.all_gather(red[0], axis)

            self._hop1_p = _sm(
                hop1_raw, (P(axis, None),), P(axis, None)
            )
            self._reduce_p = _sm(
                reduce_raw, (P(axis, None),), P(axis, None)
            )
            self._hop2_p = _sm(hop2_raw, (P(axis, None),), P())

        # final program — rebuild the gradient tree from gathered buckets
        # and run the (replicated) optimizer update
        def apply_fn(state, loss, gathered):
            flats = []
            for b, g in zip(plan.buckets, gathered):
                if mode == "off":
                    flat = g.reshape(-1)
                else:
                    codes, gs = g
                    flat = _quant.dequantize_rows_jnp(
                        codes, gs.reshape(-1, 1), mode
                    ).reshape(-1)
                flats.append(flat[: b.n])
            flat_all = (
                jnp.concatenate(flats) if len(flats) > 1 else flats[0]
            )
            leaves, off = [], 0
            for shape, dtype, sz in zip(
                plan.shapes, plan.dtypes, plan.sizes
            ):
                leaves.append(
                    flat_all[off : off + sz].reshape(shape).astype(dtype)
                )
                off += sz
            grads = jax.tree.unflatten(plan.treedef, leaves)
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            un = jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(u.astype(jnp.float32)))
                    for u in jax.tree.leaves(updates)
                )
            )
            pn = jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(p.astype(jnp.float32)))
                    for p in jax.tree.leaves(params)
                )
            )
            return (
                common.TrainState(
                    params=params, opt_state=opt_state, step=state.step + 1
                ),
                {"loss": loss, "param_norm": pn, "update_norm": un},
            )

        self._apply_p = jax.jit(
            apply_fn, donate_argnums=(0,) if self.donate_state else ()
        )

    def _armed_tracer(self) -> Optional[obs_core.Tracer]:
        """Build the journal/tracer lazily so ``trainer.obs`` can be set
        after warmup (the bench A/B pattern)."""
        if (
            self._tracer is None
            and self.obs is not None
            and self.obs.dir
        ):
            os.makedirs(self.obs.dir, exist_ok=True)
            journal = obs_core.Journal(
                os.path.join(self.obs.dir, "obs_rank0.jsonl"),
                rank=0,
                max_records=self.obs.max_records,
            )
            self._tracer = obs_core.Tracer(0, journal=journal)
        return self._tracer

    def close_obs(self) -> None:
        """Flush and close the trainer's obs journal (idempotent)."""
        if self._tracer is not None:
            self._tracer.close()
            self._tracer = None

    def wire_bytes_per_step(self) -> Optional[int]:
        """Per-worker exchange bytes per step (None until the first
        bucketed step has built the plan, or on the fused path)."""
        return (
            self._plan.wire_bytes_per_step()
            if self._plan is not None
            else None
        )

    def _timed_hop(self, prog, args, nbytes, tracer, settle):
        """Dispatch one wire-hop program. Armed: block and journal the
        wall wait as a ``send`` (dur + bytes — the roofline wire figure).
        Unarmed on the virtual CPU mesh: block without journaling (only
        one collective program may be in flight — see
        :func:`common.bound_cpu_dispatch`). On a real accelerator
        unarmed: fully async, which is where the overlap materializes."""
        if tracer is not None:
            t0 = time.perf_counter()
            out = prog(*args)
            jax.block_until_ready(out)
            tracer.journal.event(
                "send",
                tracer.clock.tick(),
                dur=time.perf_counter() - t0,
                bytes=nbytes,
            )
            return out
        out = prog(*args)
        if settle:
            jax.block_until_ready(out)
        return out

    def _bucketed_step(self, state, x, y):
        self._ensure_buckets(state.params)
        tracer = self._armed_tracer()
        armed = tracer is not None
        settle = (
            self.topo.platform == "cpu" and self.topo.num_devices > 1
        )

        def _span():
            return (
                tracer.span("compute") if armed else obs_core.NULL_SPAN
            )

        def _settle(out):
            # armed compute spans carry proof-of-completion blocking so
            # the roofline figure is device time, not dispatch time; the
            # CPU mesh additionally must not pipeline programs
            if armed or settle:
                jax.block_until_ready(out)

        with _span():
            loss, *rows = self._grads_p(state.params, x, y)
            _settle(rows)

        gathered, res_sq = [], []
        for k, row in enumerate(rows):
            b = self._plan.buckets[k]
            if self.quant != "off":
                with _span():
                    codes, scales, new_r, sq = self._encode_p(
                        row, self._residual[k]
                    )
                    _settle(codes)
                self._residual[k] = new_r
                res_sq.append(sq)
                cx, sx = self._timed_hop(
                    self._hop1_p, (codes, scales), b.hop_bytes,
                    tracer, settle,
                )
                with _span():
                    rcodes, rscale, new_r2 = self._reduce_p(
                        cx, sx, self._residual2[k]
                    )
                    _settle(rcodes)
                self._residual2[k] = new_r2
                gathered.append(
                    self._timed_hop(
                        self._hop2_p, (rcodes, rscale), b.hop_bytes,
                        tracer, settle,
                    )
                )
            else:
                xch = self._timed_hop(
                    self._hop1_p, (row,), b.hop_bytes, tracer, settle
                )
                with _span():
                    red = self._reduce_p(xch)
                    _settle(red)
                gathered.append(
                    self._timed_hop(
                        self._hop2_p, (red,), b.hop_bytes, tracer, settle
                    )
                )

        with _span():
            state, metrics = self._apply_p(state, loss, gathered)
            _settle(metrics)

        rt_numerics = (
            _runtime.active_checker() is not None
            and getattr(_runtime.active_checker(), "numerics", False)
        )
        if armed or rt_numerics:
            elastic = (
                float(
                    np.sqrt(
                        sum(
                            float(np.sum(np.asarray(s))) for s in res_sq
                        )
                    )
                )
                if res_sq
                else 0.0
            )
            # RT104 sees the SAME value the dynamics plane journals as
            # `elastic` — the sanitizer and the journal can never
            # disagree about what the EF residual norm was
            _runtime.note_residual_norm("sync-dp.elastic", elastic)
        if armed:
            self._round += 1
            pn = float(metrics["param_norm"])
            un = float(metrics["update_norm"])
            # dynamics plane (docs/OBSERVABILITY.md "dynamics"): elastic
            # = EF residual norm — bounded by the quantization grid, so a
            # healthy run equilibrates; sustained growth = the quantized
            # stream diverging from the raw one
            tracer.journal.event(
                "dynamics",
                tracer.clock.tick(),
                round=self._round,
                algo="sync-dp",
                elastic=elastic,
                push_norm=un,
                param_norm=pn,
                fetch_delta=0.0,
                ratio=un / pn if pn > 0 else 0.0,
            )
        return state, metrics

    def step(self, state, x_global, y_global):
        """One sync-DP step on a global batch (leading dim divisible by W,
        per-worker shard divisible by accum_steps)."""
        self._check(x_global)
        if self.bucketed:
            state, metrics = self._bucketed_step(state, x_global, y_global)
        else:
            tracer = self._armed_tracer()
            if tracer is not None:
                with tracer.span("compute"):
                    state, metrics = self._step(state, x_global, y_global)
                    jax.block_until_ready(metrics)
            else:
                state, metrics = self._step(state, x_global, y_global)
        common.bound_cpu_dispatch(self.topo, metrics)
        return state, metrics

    def evaluate(self, state, x, y, batch: int = 1024):
        """Full-dataset eval; returns (accuracy, mean_loss)."""
        correct, loss_sum, n = common.batched_count_eval(
            self._eval, state.params, x, y, batch, self.topo.num_workers
        )
        return correct / n, loss_sum / n

    def fit(
        self,
        batches,
        state,
        epochs: int = 1,
        log_every: int = 0,
        start_epoch: int = 0,
        skip_steps: int = 0,
        on_step=None,
        prefetch: int = 2,
    ):
        """Epoch loop over a :class:`mpit_tpu.data.Batches` — the shared
        :func:`common.synced_fit_loop` with the sync-DP sharding/check.
        Returns (state, last_metrics)."""
        step_fn = self._bucketed_step if self.bucketed else self._step
        return common.synced_fit_loop(
            self.topo, step_fn, batches, state,
            sharding=self.topo.worker_sharding(),
            check=self._check,
            log_tag="sync-dp",
            epochs=epochs, log_every=log_every, start_epoch=start_epoch,
            skip_steps=skip_steps, on_step=on_step, prefetch=prefetch,
        )
