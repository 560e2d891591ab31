"""Training driver: one function from :class:`TrainConfig` to results.

This is the framework's equivalent of the reference's example-script layer
(SURVEY.md §2 comp. 6) factored into the library, so every BASELINE workload
config is one preset away and the example CLIs stay thin. The loop wires in
everything the reference lacked (SURVEY.md §5): JSONL metrics, step timing,
profiler traces, checkpoint/resume.
"""

from __future__ import annotations

import json
import time
from typing import Optional

import numpy as np

from mpit_tpu.utils.config import TrainConfig


def _load_dataset(cfg: TrainConfig):
    """(x_train, y_train, x_test, y_test, meta) for the config's dataset;
    ``meta`` carries dataset facts the model needs (e.g. vocab_size)."""
    from mpit_tpu.data import (
        load_cifar10,
        load_imagenet_like,
        load_mnist,
    )

    if cfg.dataset == "mnist":
        return (*load_mnist(synthetic_train=cfg.train_size), {})
    if cfg.dataset == "cifar10":
        return (*load_cifar10(synthetic_train=cfg.train_size), {})
    if cfg.dataset == "imagenet":
        return (
            *load_imagenet_like(
                synthetic_train=cfg.train_size,
                synthetic_test=max(cfg.train_size // 4, 64),
                image_size=cfg.image_size,
            ),
            {},
        )
    if cfg.dataset == "ptb":
        return _ptb_windows(cfg)
    raise ValueError(f"unknown dataset {cfg.dataset!r}")


def _ptb_windows(cfg: TrainConfig):
    """Token stream → (N, T) next-token windows: x=tokens[i:i+T],
    y=tokens[i+1:i+T+1] (the LM objective over fixed-length unrolls)."""
    from mpit_tpu.data import load_ptb

    t_len = cfg.seq_len
    need = (cfg.train_size + 1) * t_len + 1
    train_toks, valid_toks, vocab = load_ptb(
        synthetic_tokens=max(need + need // 8, 20_000)
    )

    def windows(toks: np.ndarray):
        n = (len(toks) - 1) // t_len
        x = toks[: n * t_len].reshape(n, t_len)
        y = toks[1 : n * t_len + 1].reshape(n, t_len)
        return x.astype(np.int32), y.astype(np.int32)

    x_tr, y_tr = windows(train_toks)
    x_va, y_va = windows(valid_toks)
    return (
        x_tr[: cfg.train_size],
        y_tr[: cfg.train_size],
        x_va,
        y_va,
        {"vocab_size": vocab},
    )


def _build_model(cfg: TrainConfig, meta: dict, worker_axis: str = None):
    from mpit_tpu.comm.topology import WORKER_AXIS
    from mpit_tpu.models import REMAT_MODELS, STEM_MODELS, get_model

    if worker_axis is None:
        worker_axis = WORKER_AXIS

    name = cfg.model.lower()  # the registry lowercases; match it
    algo = cfg.resolved_algo()
    if cfg.remat and name not in REMAT_MODELS:
        import warnings

        warnings.warn(
            f"remat is implemented for {REMAT_MODELS} only; model "
            f"{cfg.model!r} runs without it",
            stacklevel=2,
        )
    if cfg.arch is not None:
        if name != "transformer":
            raise ValueError(
                f"arch describes a transformer; model={cfg.model!r} cannot "
                "take it"
            )
        if algo != "sync":
            raise ValueError(
                f"algo={cfg.algo!r} is not built for a model described by "
                "arch: only sync runs it (its step carries the routing "
                "counters and its state is made in one program); seq-sync, "
                "moe-sync and pp-sync shard the GPT-2 block by its own "
                "parameter names"
            )
    if cfg.moe_experts and (
        cfg.arch is not None
        or not (name == "transformer" and algo == "moe-sync")
    ):
        import warnings

        warnings.warn(
            f"moe_experts={cfg.moe_experts} is the GShard expert layer of "
            "the GPT-2 block and only applies with model='transformer', "
            f"algo='moe-sync' and no arch; model={cfg.model!r} "
            f"algo={cfg.algo!r} runs without it (an arch brings its own "
            "experts)",
            stacklevel=2,
        )
    if cfg.seq_impl != "ring" and algo != "seq-sync":
        import warnings

        warnings.warn(
            f"seq_impl={cfg.seq_impl!r} only applies with algo='seq-sync' "
            f"(no sequence axis exists under algo={cfg.algo!r}); running "
            "plain dense attention",
            stacklevel=2,
        )
    if name == "transformer" and cfg.arch is not None:
        return get_model(
            cfg.model,
            vocab_size=meta.get("vocab_size", 10_000),
            arch=cfg.arch,
            remat=cfg.remat,
            attn_impl=cfg.attn_impl,
        )
    if name == "transformer":
        return get_model(
            cfg.model,
            vocab_size=meta.get("vocab_size", 10_000),
            num_layers=cfg.layers,
            d_model=cfg.d_model,
            num_heads=cfg.heads,
            d_ff=cfg.d_ff,
            max_len=max(cfg.seq_len, 32),
            # seq-sync applies the model inside shard_map with the sequence
            # sharded on the mesh's "sp" axis (ring attention); moe-sync
            # shards experts over the worker axis
            seq_axis="sp" if algo == "seq-sync" else None,
            seq_impl=cfg.seq_impl,
            remat=cfg.remat,
            attn_impl=cfg.attn_impl,
            **(
                {
                    "moe_experts": cfg.moe_experts,
                    "moe_axis": worker_axis,
                    "moe_capacity_factor": cfg.moe_capacity_factor,
                    "moe_top_k": cfg.moe_top_k,
                    "moe_balance_weight": cfg.moe_balance_weight,
                    "moe_zloss_weight": cfg.moe_zloss_weight,
                }
                if algo == "moe-sync"
                else {}
            ),
        )
    if name in ("lstm", "lstm_lm", "ptb_lstm"):
        return get_model(cfg.model, vocab_size=meta.get("vocab_size", 10_000))
    # capability kwargs derive from the registry lists — the ONE source of
    # which model takes which flag
    kwargs = {}
    if name in STEM_MODELS:
        kwargs["stem"] = cfg.stem
    if name in REMAT_MODELS:
        kwargs["remat"] = cfg.remat
    return get_model(cfg.model, **kwargs)


# the per-step (no τ-round) algos — ONE copy; bench.py imports these so
# its mesh/τ handling can never drift from the driver's
SYNC_ALGOS = ("sync", "zero-sync", "seq-sync", "moe-sync", "pp-sync")


def second_axis_for(cfg: TrainConfig) -> dict:
    """algo -> (second mesh-axis name, configured extent) for the 2-D
    mesh algos; the ONE copy bench.py and _world_for share."""
    return {"seq-sync": ("sp", cfg.sp), "pp-sync": ("pp", cfg.pp)}


def build_optimizer(cfg: TrainConfig, total_updates: int):
    """The config's optax optimizer + schedule (the ONE construction the
    driver, PS path, and bench harness share).

    ``total_updates``: optimizer-update count the cosine decays over —
    for τ-round trainers that is LOCAL steps (the local optimizer updates
    every step), for sync trainers it equals the step count.
    """
    import optax

    total = max(int(total_updates), 2)  # optax needs decay_steps > 0
    if cfg.lr_schedule == "constant":
        lr = cfg.lr
    elif cfg.lr_schedule == "cosine":
        lr = optax.cosine_decay_schedule(cfg.lr, total)
    elif cfg.lr_schedule == "warmup-cosine":
        warm = min(cfg.warmup_steps, total - 1)  # strictly < total
        lr = optax.warmup_cosine_decay_schedule(
            0.0, cfg.lr, warm, total
        )
    else:
        raise ValueError(
            f"unknown lr_schedule {cfg.lr_schedule!r}; have: constant, "
            "cosine, warmup-cosine"
        )
    if cfg.optimizer == "sgd":
        opt = optax.sgd(lr, momentum=cfg.momentum)
    elif cfg.optimizer == "adam":
        opt = optax.adam(lr)
    elif cfg.optimizer == "adamw":
        opt = optax.adamw(lr, weight_decay=cfg.weight_decay)
    else:
        raise ValueError(
            f"unknown optimizer {cfg.optimizer!r}; have: sgd, adam, adamw"
        )
    # --clip-norm: chain the optax transform wherever the update sees
    # consistent gradients (sync/seq/tp: reduced before update;
    # easgd/downpour/ps-*: per-worker local updates, so a per-worker
    # clip IS the async semantics). moe-sync/zero-sync updates run on
    # device-varying gradients — their trainers take clip_norm directly
    # (mesh-correct psum'd norm) and their constructors REJECT this
    # chain, so the driver must not install it there. pp-sync is in the
    # same boat: its trainer receives this optimizer and applies it on
    # stage-sharded block gradients inside shard_map (the probe would
    # reject the chain), so it too takes clip_norm= directly.
    if cfg.clip_norm is not None and cfg.resolved_algo() not in (
        "moe-sync", "zero-sync", "pp-sync"
    ):
        opt = optax.chain(optax.clip_by_global_norm(cfg.clip_norm), opt)
    return opt


def build_trainer(cfg: TrainConfig, model, opt, topo):
    """Collective trainer for ``cfg.algo`` (the single algo→trainer mapping;
    the bench harness reuses it so both measure the exact same construction)."""
    from mpit_tpu.parallel import (
        DataParallelTrainer,
        DownpourTrainer,
        EASGDTrainer,
        SeqParallelTrainer,
    )

    if cfg.exchange_dtype not in ("none", "bf16"):
        raise ValueError(
            f"unknown exchange_dtype {cfg.exchange_dtype!r}; have: none, bf16"
        )
    algo = cfg.resolved_algo()
    if cfg.grad_accum > 1 and algo not in ("sync", "zero-sync"):
        import warnings

        warnings.warn(
            f"grad_accum={cfg.grad_accum} applies to algo='sync' and "
            f"'zero-sync' only; algo={cfg.algo!r} runs without "
            "accumulation",
            stacklevel=2,
        )
    if cfg.exchange_dtype != "none" and algo != "easgd":
        import warnings

        warnings.warn(
            f"exchange_dtype={cfg.exchange_dtype!r} only applies to the "
            f"easgd/eamsgd exchange collective; algo={cfg.algo!r} runs "
            "full-precision (flag ignored)",
            stacklevel=2,
        )
    if algo == "easgd":
        import jax.numpy as jnp

        xdtype = jnp.bfloat16 if cfg.exchange_dtype == "bf16" else None
        return EASGDTrainer(model, opt, topo, alpha=cfg.alpha, tau=cfg.tau,
                            exchange_dtype=xdtype)
    if algo == "downpour":
        return DownpourTrainer(model, opt, topo, tau=cfg.tau,
                               staleness=cfg.staleness)
    if algo == "sync":
        return DataParallelTrainer(model, opt, topo,
                                   accum_steps=cfg.grad_accum,
                                   jit_init=cfg.arch is not None)
    if algo == "zero-sync":
        from mpit_tpu.parallel import ZeroDataParallelTrainer

        return ZeroDataParallelTrainer(model, opt, topo,
                                       accum_steps=cfg.grad_accum,
                                       clip_norm=cfg.clip_norm)
    if algo == "seq-sync":
        return SeqParallelTrainer(model, opt, topo)
    if algo == "moe-sync":
        from mpit_tpu.parallel import MoEParallelTrainer

        if not cfg.moe_experts:
            raise ValueError(
                "algo='moe-sync' needs --moe-experts > 0 (and model="
                "transformer)"
            )
        return MoEParallelTrainer(model, opt, topo,
                                  clip_norm=cfg.clip_norm)
    if algo == "pp-sync":
        from mpit_tpu.parallel import PipelineParallelTrainer

        if cfg.model.lower() != "transformer":
            raise ValueError(
                "algo='pp-sync' is transformer-only (the pipeline stages "
                f"a transformer layer stack); got model={cfg.model!r}"
            )
        ignored = [
            f for f, on in (
                ("attn_impl", cfg.attn_impl != "xla"),
                ("remat", cfg.remat),
            ) if on
        ]
        if ignored:
            import warnings

            warnings.warn(
                f"pp-sync builds its own f32 dense-attention pipeline "
                f"model; {ignored} do not apply and are ignored",
                stacklevel=2,
            )
        # the pipeline builds its own stacked-leaf params; shapes come
        # off the flax model so one --model transformer config drives
        # every trainer. It takes the SAME optax optimizer run() builds
        # for everyone (elementwise — probe-enforced) and the
        # mesh-correct clip_norm (the optax chain must NOT be installed
        # for pp-sync; build_optimizer excludes it).
        return PipelineParallelTrainer(
            vocab_size=model.vocab_size,
            num_layers=model.num_layers,
            d_model=model.d_model,
            num_heads=model.num_heads,
            seq_len=model.max_len,
            d_ff=model.d_ff,
            topo=topo,
            n_micro=cfg.n_micro,
            optimizer=opt,
            clip_norm=cfg.clip_norm,
            schedule=cfg.pp_schedule,
            virtual=cfg.pp_virtual,
        )
    raise ValueError(f"unknown algo {cfg.algo!r}")


def _world_for(cfg: TrainConfig):
    """The topology ``cfg`` needs, rebuilding the world when the pinned one
    does not fit (seq-sync wants a 2-D dp×sp mesh with the configured sp
    extent; everything else wants an effectively 1-D worker mesh)."""
    import jax

    import mpit_tpu
    # direct from the submodule: the comm package re-exports topology (the
    # function), shadowing the submodule attribute of the same name
    from mpit_tpu.comm.topology import is_initialized
    from mpit_tpu.comm.topology import topology as current_topology

    algo = cfg.resolved_algo()
    second_axis = second_axis_for(cfg)
    if is_initialized():
        cur = current_topology()
        names = cur.mesh.axis_names
        shape = cur.mesh.devices.shape
        if algo in second_axis:
            ax, extent = second_axis[algo]
            fits = names[:2] == ("dp", ax) and shape[1] == extent
        else:
            fits = all(n == 1 for n in shape[1:])
        if fits:
            return cur
        mpit_tpu.finalize()
    if algo in second_axis:
        ax, extent = second_axis[algo]
        n = len(jax.devices())
        if n % extent:
            raise ValueError(
                f"{ax}={extent} does not divide the {n} available devices"
            )
        return mpit_tpu.init(
            axis_names=("dp", ax), mesh_shape=(n // extent, extent)
        )
    return mpit_tpu.init()


def _check_resume_layout(cfg: TrainConfig) -> None:
    """Refuse a resume whose checkpoint was written under a different
    param LAYOUT. The pipeline stores its layer stack chunk-permuted
    under interleaving, and a different pp extent re-shards the stack —
    shapes match either way, so from_bytes would happily load layers in
    the wrong order and train a silently-wrong model."""
    import json as _json
    import os as _os

    from mpit_tpu.utils import latest_checkpoint

    step = latest_checkpoint(cfg.ckpt_dir)
    if step is None:
        return
    meta_path = _os.path.join(cfg.ckpt_dir, f"ckpt_{step:08d}.json")
    if not _os.path.exists(meta_path):
        return
    saved = _json.loads(
        _json.load(open(meta_path)).get("config", "{}")
    )
    if saved.get("algo") != cfg.algo:
        return  # cross-algo restore fails on structure already
    # EVERY resuming trainer checkpoints an optax opt_state whose pytree
    # STRUCTURE depends on: the optimizer (adam's two moments vs sgd's
    # trace), whether the lr is a SCHEDULE (scale_by_schedule carries a
    # count leaf; a constant lr doesn't), and — where build_optimizer
    # chains it — whether clip_norm is set (the chain's state tuple gains
    # an element). from_bytes reports any of these as an opaque structure
    # error, so catch them here for ALL algos, not just pp-sync. Value-
    # only changes (lr, clip threshold, cosine<->warmup-cosine, momentum:
    # optax.sgd builds a TraceState for any non-None float, 0.0 included)
    # are structure-identical and stay resumable.
    clip_chained = cfg.resolved_algo() not in (
        "moe-sync", "zero-sync", "pp-sync"  # these take clip_norm on the
    )  # trainer, outside opt_state (build_optimizer's chain comment)
    structure_of = lambda opt, sched, clip: {
        "optimizer": opt,
        "lr_is_schedule": sched != "constant",
        **({"clip_chained": clip is not None} if clip_chained else {}),
    }
    cur = structure_of(cfg.optimizer, cfg.lr_schedule, cfg.clip_norm)
    # old metadata-less fields: compare only what the checkpoint recorded
    sav = structure_of(
        saved.get("optimizer", cfg.optimizer),
        saved.get("lr_schedule", cfg.lr_schedule),
        saved.get("clip_norm", cfg.clip_norm),
    )
    if sav != cur:
        diff = {k: (sav[k], cur[k]) for k in cur if sav[k] != cur[k]}
        raise ValueError(
            f"resume layout mismatch: checkpoint in {cfg.ckpt_dir!r} was "
            f"written with a different optimizer-state structure "
            f"{diff} (saved, requested) — restore with the original "
            "optimizer/lr_schedule/clip_norm configuration or start fresh"
        )
    if cfg.algo != "pp-sync":
        return
    # state-LAYOUT generation check: the pipeline state moved from
    # {params, momentum, step} (built-in SGD) to {params, opt_state,
    # step} (optax path). The config looks identical across that code
    # change, so peek at the serialized top-level keys and fail clearly
    # instead of deep inside from_bytes.
    from mpit_tpu.utils.checkpoint import _ckpt_path

    try:
        # stream ONLY the top-level map keys — deserializing the full
        # tree here would double resume I/O and spike host memory just
        # to look at three strings
        import msgpack

        with open(_ckpt_path(cfg.ckpt_dir, step), "rb") as f:
            unp = msgpack.Unpacker(f, raw=False)
            keys = set()
            for _ in range(unp.read_map_header()):
                keys.add(unp.unpack())
                unp.skip()
    except Exception:
        keys = None
    if keys is not None and "momentum" in keys and "opt_state" not in keys:
        raise ValueError(
            f"checkpoint step {step} in {cfg.ckpt_dir} stores the "
            "pre-optax pipeline state layout {params, momentum, step}; "
            "the current pp-sync trainer keeps {params, opt_state, "
            "step}. Restart training (or restore with an old build) — "
            "resuming across this layout change is not supported."
        )
    # only interleaving permutes storage: under gpipe/1f1b the stacked
    # layers are globally ordered, so a different pp extent re-shards
    # soundly on restore and a gpipe<->1f1b flip is layout-identical.
    # layers always matters (it changes the array shapes — fail clearly
    # here, not inside from_bytes). Optimizer structure was checked above
    # for every algo.
    fields = ["layers", "pp_schedule"]
    if "interleaved" in (saved.get("pp_schedule"), cfg.pp_schedule):
        fields += ["pp", "pp_virtual"]
    mismatched = {
        f: (saved.get(f), getattr(cfg, f))
        for f in fields
        if f in saved and saved.get(f) != getattr(cfg, f)
    }
    if set(mismatched) == {"pp_schedule"} and "interleaved" not in (
        saved.get("pp_schedule"), cfg.pp_schedule
    ):
        return
    if mismatched:
        raise ValueError(
            f"resume layout mismatch: checkpoint in {cfg.ckpt_dir!r} was "
            f"written with {mismatched} (saved, requested) — the pipeline "
            "param/opt-state layout depends on these; restore with the "
            "original config or start fresh"
        )


def run(cfg: TrainConfig) -> dict:
    """Train per ``cfg``; returns a results dict (acc, loss, throughput...).

    The driver builds the world itself (idempotent when a fitting topology
    exists; a non-fitting pinned mesh — e.g. a leftover 2-D seq-sync mesh —
    is finalized and rebuilt, see :func:`_world_for`).
    """
    import jax

    import mpit_tpu
    from mpit_tpu.data import Batches
    from mpit_tpu.utils import (
        MetricsLogger,
        force_completion,
        latest_checkpoint,
        profiling,
        restore_checkpoint,
        save_checkpoint,
        trace,
    )

    topo = _world_for(cfg)
    x_tr, y_tr, x_te, y_te, meta = _load_dataset(cfg)
    from mpit_tpu.data import cast_input_dtype

    # train inputs only: eval accumulates in float32 regardless, and the
    # staging win is per-step HBM/transfer traffic, which eval doesn't pay
    x_tr = cast_input_dtype(x_tr, cfg.input_dtype)
    is_seq = cfg.dataset == "ptb"
    model = _build_model(cfg, meta, worker_axis=topo.worker_axis)
    # cosine horizon: PS clients count LOCAL steps; everyone else counts
    # fit-loop units x (τ local updates per unit for the round trainers)
    if cfg.algo.startswith("ps-"):
        total_updates = cfg.steps
    else:
        steps_per_epoch = max(
            len(x_tr) // max(cfg.global_batch, 1), 1
        )
        total_updates = cfg.epochs * steps_per_epoch
    opt = build_optimizer(cfg, total_updates)

    log = MetricsLogger(path=cfg.metrics_path, tag=cfg.algo, echo=False)
    results: dict = {"config": cfg.to_json(), "workers": topo.num_workers,
                     "platform": topo.platform}

    if cfg.algo.startswith("ps-"):
        return _run_async_ps(cfg, model, opt, x_tr, y_tr, x_te, y_te,
                             log, results)

    trainer = build_trainer(cfg, model, opt, topo)

    gb = max(cfg.global_batch // topo.num_workers, 1) * topo.num_workers
    state = trainer.init_state(jax.random.key(cfg.seed), x_tr[:2])

    start_unit = 0
    if cfg.resume and cfg.ckpt_dir:
        _check_resume_layout(cfg)
        template = state
        shardings = jax.tree.map(lambda a: a.sharding, template)
        state, step = restore_checkpoint(cfg.ckpt_dir, template,
                                         shardings=shardings)
        if step is not None:
            start_unit = step
            results["resumed_from"] = step

    batches = Batches(x_tr, y_tr, global_batch=gb, seed=cfg.seed)
    is_sync = cfg.resolved_algo() in SYNC_ALGOS
    tau = 1 if is_sync else cfg.tau
    units_per_epoch = batches.steps_per_epoch() // tau
    if units_per_epoch == 0:
        raise ValueError(
            f"epoch of {batches.steps_per_epoch()} step(s) cannot fill one "
            f"{'step' if is_sync else f'round of tau={tau}'}"
        )
    # resume re-enters the SAME deterministic data schedule: unit counters
    # map back to (epoch, offset); cfg.epochs is total, not additional
    start_epoch, skip_units = divmod(start_unit, units_per_epoch)
    unit = start_unit  # steps (sync) or rounds (easgd/downpour)
    metrics = None

    def on_unit(_done, st, m):
        nonlocal unit, metrics
        unit += 1
        metrics = m
        if cfg.log_every and unit % cfg.log_every == 0:
            log.log(unit, loss=m["loss"])
        if cfg.ckpt_dir and cfg.ckpt_every and unit % cfg.ckpt_every == 0:
            save_checkpoint(cfg.ckpt_dir, st, step=unit,
                            metadata={"config": cfg.to_json()})

    t_start = time.perf_counter()
    with trace(cfg.profile_dir):
        if is_sync:
            state, metrics = trainer.fit(
                batches, state, epochs=cfg.epochs, start_epoch=start_epoch,
                skip_steps=skip_units, on_step=on_unit,
                prefetch=cfg.prefetch,
            )
        else:
            state, metrics = trainer.fit(
                batches, state, epochs=cfg.epochs, start_epoch=start_epoch,
                skip_rounds=skip_units, on_round=on_unit,
                prefetch=cfg.prefetch,
            )
        if metrics is not None:
            # completion barrier covering BOTH the final state and the
            # last metrics (the loss alone would not prove the state
            # update finished)
            force_completion(state, metrics)
    wall = time.perf_counter() - t_start
    trained = unit - start_unit
    samples = trained * tau * gb
    if cfg.ckpt_dir and trained:
        save_checkpoint(cfg.ckpt_dir, state, step=unit,
                        metadata={"config": cfg.to_json()})

    if is_sync:
        acc, eval_loss = trainer.evaluate(state, x_te, y_te)
        results["eval_loss"] = eval_loss
    else:
        acc = trainer.evaluate(state, x_te, y_te)
    if is_seq and cfg.resolved_algo() not in (
        "seq-sync", "moe-sync", "pp-sync"
    ):
        # eval counts correct *tokens* per window; the seq/moe/pp-sync
        # trainers already normalize per token themselves
        acc = acc / cfg.seq_len
    results.update(
        accuracy=acc,
        final_loss=float(metrics["loss"]) if metrics is not None else None,
        # the fewest distinct devices any leaf of the final state has
        # shards (or replicas) on: == num_devices when every chip of the
        # mesh holds its part of the state
        state_min_devices=min(
            len({s.device for s in leaf.addressable_shards})
            for leaf in jax.tree.leaves(state)
            if isinstance(leaf, jax.Array)
        ),
        trained_units=trained,
        samples=samples,
        wall_s=wall,
        samples_per_sec=samples / wall,
        # per DEVICE, not per worker-axis entry: on seq-sync's 2-D mesh all
        # dp*sp chips execute the step (identical on 1-D meshes)
        samples_per_sec_per_chip=samples / wall / topo.num_devices,
        step_time={"steps": trained,
                   "mean_s": wall / trained if trained else None},
        last_checkpoint=(latest_checkpoint(cfg.ckpt_dir)
                         if cfg.ckpt_dir else None),
        # the host spans of fit and set-up (docs/OBSERVABILITY.md), without
        # the ring of single durations: main() prints this dict as one line
        spans={
            name: {k: v for k, v in rec.items() if k != "last_s"}
            for name, rec in profiling.snapshot().items()
        },
    )
    log.close()
    return results


def _run_async_ps(cfg, model, opt, x_tr, y_tr, x_te, y_te, log, results):
    """The reference's literal pclient/pserver shape (BASELINE.json:7).

    Aux-flag support in this mode (round-1 advisor: these used to be silent
    no-ops): ``profile_dir`` traces the whole async run; ``ckpt_dir`` makes
    every server persist its center chunk (elastic recovery — every
    ``ckpt_every`` updates and at teardown) plus the final msgpack center
    checkpoint; ``resume`` restores the persisted chunks so a restarted
    job continues from the last center; ``log_every`` logs the per-step
    client losses post-hoc (there is no global step during the run —
    clients are asynchronous by design). ``grad_accum`` has no meaning
    here and WARNs instead of silently ignoring."""
    import warnings

    from mpit_tpu.parallel import AsyncPSTrainer
    from mpit_tpu.utils import save_checkpoint, trace

    for flag, on in (
        ("grad_accum", cfg.grad_accum > 1),
    ):
        if on:
            warnings.warn(
                f"{flag!r} is not supported with algo={cfg.algo!r} "
                "(async PS clients run their own local steps); ignoring",
                stacklevel=3,
            )
    if cfg.exchange_dtype not in ("none", "bf16"):
        raise ValueError(
            f"unknown exchange_dtype {cfg.exchange_dtype!r}; have: none, bf16"
        )
    if cfg.exchange_dtype != "none":
        warnings.warn(
            "exchange_dtype compresses the collective easgd exchange; the "
            "host-async PS protocol serializes parameters on its own path "
            "and ignores it",
            stacklevel=3,
        )
    ps_algo = cfg.resolved_algo().removeprefix("ps-")
    alpha = cfg.alpha if cfg.alpha is not None else 0.9 / cfg.clients
    trainer = AsyncPSTrainer(
        model, opt,
        num_clients=cfg.clients, num_servers=cfg.servers,
        algo=ps_algo,
        alpha=alpha, tau=cfg.tau,
        transport=cfg.transport,
        client_timeout=cfg.client_timeout,
        ckpt_dir=cfg.ckpt_dir or None,
        # config semantics: ckpt_every=0 means "no periodic writes" —
        # servers then persist only at teardown, never every-100 default
        ckpt_every=cfg.ckpt_every or None,
        resume=cfg.resume,
    )
    per_client = max(cfg.global_batch // cfg.clients, 1)
    t0 = time.perf_counter()
    with trace(cfg.profile_dir):
        center, stats = trainer.train(
            x_tr, y_tr, steps=cfg.steps, batch_size=per_client, seed=cfg.seed
        )
    wall = time.perf_counter() - t0
    acc = trainer.evaluate(center, x_te, y_te)
    if cfg.dataset == "ptb":
        acc = acc / cfg.seq_len
    samples = cfg.steps * per_client * cfg.clients
    if cfg.log_every:
        # stop before the final step — the summary line below logs it
        for s in range(cfg.log_every - 1, cfg.steps - 1, cfg.log_every):
            step_losses = [l[s] for l in stats["losses"] if len(l) > s]
            if step_losses:
                log.log(s + 1, loss=float(np.mean(step_losses)))
    log.log(cfg.steps, loss=stats["mean_final_loss"], accuracy=acc)
    if cfg.ckpt_dir:
        save_checkpoint(
            cfg.ckpt_dir, center, step=cfg.steps,
            metadata={"config": cfg.to_json(), "kind": "ps_center"},
        )
        results["last_checkpoint"] = cfg.steps
    results.update(
        accuracy=acc,
        final_loss=stats["mean_final_loss"],
        transport=stats["transport"],
        client_devices=stats["client_devices"],
        server_counts=stats["server_counts"],
        dead_clients=stats["dead_clients"],
        center_restored=stats["center_restored"],
        samples=samples,
        wall_s=wall,
        samples_per_sec=samples / wall,
        clients=cfg.clients,
        servers=cfg.servers,
    )
    log.close()
    return results


def main(argv=None, description: Optional[str] = None) -> None:
    """CLI over every BASELINE workload config (installed as ``mpit-train``;
    ``examples/train.py`` is the same entry run from a checkout, passing its
    usage docstring as ``description``). Prints the results dict as one JSON
    line."""
    cfg = TrainConfig.from_args(
        argv,
        description=description
        or "mpit_tpu training driver — any preset, any flag override "
        "(e.g. --preset mnist-easgd --epochs 10). On the CPU-simulated "
        "mesh, prefix with XLA_FLAGS=--xla_force_host_platform_device_"
        "count=8 JAX_PLATFORMS=cpu.",
    )

    from mpit_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(json.dumps(run(cfg), default=repr))
