"""Shared role bodies for the host-async PS protocol.

One implementation of the client training loop, used by BOTH runtimes that
the reference's single Lua codebase served (SURVEY.md §2 comps. 3-6):

- thread mode — :class:`mpit_tpu.parallel.AsyncPSTrainer` (brokered
  in-process transports, the default examples), and
- process mode — ``examples/ptest_proc.py`` under ``python -m
  mpit_tpu.launch -n N`` (one OS process per rank over TCP, the literal
  ``mpirun`` shape).

Keeping the protocol body in one place is what guarantees the two modes
stay protocol-identical.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Optional

import jax
import numpy as np
import optax

from mpit_tpu.obs.core import span as obs_span
from mpit_tpu.obs.live import (
    M_COMPUTE_S,
    M_ELASTIC_DIST,
    M_EXCHANGE_FAILURES,
    M_EXCHANGE_LAT,
    M_EXCHANGE_S,
    M_NORM_RATIO,
    M_PARAM_NORM,
    M_PUSHES,
    M_PUSH_NORM,
    M_REPAIRED_CHUNKS,
    M_ROUNDS,
    M_SAMPLES,
    M_SKIPPED_ROUNDS,
    M_STALE_PARAMS,
    M_STEPS,
    live_registry,
)
from mpit_tpu.parallel import common
from mpit_tpu.parallel.pclient import PClient
from mpit_tpu.transport import RecvTimeout
from mpit_tpu.utils.params import FlatParamSpec, unflatten_params
from mpit_tpu.utils.profiling import force_completion

logger = logging.getLogger("mpit_tpu.parallel.ps_roles")

# mpit-analysis: protocol-role[client->server]
# (shared client-role body for both runtimes; its transport traffic all
# flows through PClient, so MPT008 merges this module into the client
# role's op set)


def make_local_step(
    model, optimizer: optax.GradientTransformation,
    loss_fn: Optional[Callable] = None,
):
    """Jitted ``(params, opt_state, x, y) -> (params, opt_state, loss)`` —
    the client's on-device compute between exchanges."""
    loss_fn = (
        loss_fn if loss_fn is not None else common.default_loss_fn(model.apply)
    )

    def local_step(params, opt_state, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = optimizer.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(local_step)


def _record_dynamics(
    transport,
    reg,
    round_no: int,
    algo: str,
    flat: np.ndarray,
    center: np.ndarray,
    prev_center: Optional[np.ndarray],
    push_vec: Optional[np.ndarray] = None,
    alpha: Optional[float] = None,
) -> None:
    """Per-exchange training-dynamics record (docs/OBSERVABILITY.md
    "dynamics"): elastic distance ‖x_local − x̃‖ — THE quantity the EASGD
    analysis bounds — plus push-delta norm, fetch-delta norm (how far
    the center moved since this client's previous pull), param norm, and
    the update/param norm ratio.

    Every input is host numpy the exchange already materialized (the
    τ-boundary flatten and the fetched center), so this adds ZERO device
    syncs; it lives outside the training loop so MPT005 stays clean, and
    the caller only invokes it when the transport is obs-wrapped — the
    obs-off cost is one attribute check per round (pinned by
    tests/test_dynamics.py).

    ``push_vec`` (downpour) is the pushed delta; for EASGD the push is
    the elastic move itself, so ``alpha`` is passed instead and
    push_norm = alpha·elastic without forming another vector.
    """
    elastic = float(np.linalg.norm(flat - center))
    push_norm = (
        float(np.linalg.norm(push_vec)) if push_vec is not None
        else float(alpha) * elastic
    )
    param_norm = float(np.linalg.norm(flat))
    fetch_delta = (
        0.0 if prev_center is None
        else float(np.linalg.norm(center - prev_center))
    )
    ratio = push_norm / param_norm if param_norm > 0.0 else 0.0
    tracer = getattr(transport, "obs_tracer", None)
    if tracer is not None and tracer.journal is not None:
        tracer.journal.event(
            "dynamics",
            tracer.clock.tick(),
            round=round_no,
            algo=algo,
            elastic=elastic,
            push_norm=push_norm,
            param_norm=param_norm,
            fetch_delta=fetch_delta,
            ratio=ratio,
        )
    reg.set_gauge(M_ELASTIC_DIST, elastic)
    reg.set_gauge(M_PUSH_NORM, push_norm)
    reg.set_gauge(M_PARAM_NORM, param_norm)
    reg.set_gauge(M_NORM_RATIO, ratio)


def client_train_loop(
    client: PClient,
    local_step,
    optimizer: optax.GradientTransformation,
    spec: FlatParamSpec,
    x: np.ndarray,
    y: np.ndarray,
    steps: int,
    batch_size: int,
    tau: int,
    algo: str,
    alpha: float,
    seed: int,
    max_exchange_failures: Optional[int] = None,
    exchange_stats: Optional[dict] = None,
    join: bool = False,
) -> list[float]:
    """The pclient side of SURVEY.md §3(b): τ jit-compiled local steps, then
    push/pull per ``algo`` ("easgd" or "downpour"). Returns per-step losses.
    Does NOT send stop — the caller owns teardown (it may want a final
    ``client.fetch()`` for evaluation first).

    Graceful degradation (docs/ROBUSTNESS.md): with
    ``max_exchange_failures`` set, a failed exchange (timeout after the
    client's retries, or a transport error) logs, SKIPS the round — the
    client keeps training on its local params against the stale center —
    and only escalates once that many *consecutive* rounds have failed
    (any success resets the count). ``None`` keeps fail-fast semantics.
    ``exchange_stats`` (when provided) is filled with
    ``{"skipped_rounds", "exchange_failures", "repaired_chunks"}`` totals
    (``repaired_chunks``: shards rerouted off dead servers by ring-mode
    partial-scatter repair — 0 in legacy flat mode) and ``"device"``, the
    device the client's parameters ended on.

    ``join``: announce this client via the elastic-membership JOIN
    envelope for its initial pull instead of a plain fetch — required
    for elastic runs (a respawned replacement process must register its
    fresh push-identity epoch with the server; docs/ROBUSTNESS.md).
    Off by default: non-elastic runs keep their exact fetch counts.

    Loss scalars stay ON DEVICE between exchanges and are host-fetched in
    one batched transfer at each τ boundary (where the param flatten
    already forces completion) — a per-step ``float(loss)`` would stall
    the XLA dispatch pipeline every step and time the host round-trip
    rather than the training.

    Roofline instrumentation (docs/OBSERVABILITY.md): each τ-block of
    local steps runs inside a ``"compute"`` span that ends with
    :func:`force_completion` — proof-of-completion blocking, so the span
    records real device time rather than async dispatch time. The barrier
    is conditional on the span being live (``ctx is not None``): with obs
    off the loop keeps the free-running dispatch pipeline unchanged.
    """
    import jax.numpy as jnp

    from mpit_tpu.utils.params import flatten_params

    rng = np.random.default_rng(seed)
    # live-metrics hook: NULL_REGISTRY unless MPIT_OBS_LIVE armed the
    # transport (docs/OBSERVABILITY.md "live") — publishes below are
    # unconditional, the disabled path is a no-op method call per round
    reg = live_registry(client.transport)
    # obs_span is the no-op NULL_SPAN unless the transport is obs-wrapped
    # (docs/OBSERVABILITY.md) — each span groups one exchange's wire
    # traffic under a single trace on the merged timeline
    with obs_span(client.transport, "initial_fetch"):
        # startup patience: the initial pull races server startup (under
        # a process launcher peers come up seconds apart, and a short
        # MPIT_CONNECT_RETRY_S narrows the transport's own grace). A
        # client that comes up before its servers must wait, not die —
        # unlike mid-run failures, there is no stale center to fall back
        # on yet, so keep re-asking until the deadline
        deadline = time.monotonic() + 60.0
        while True:
            try:
                initial = client.join() if join else client.fetch()
                break
            except (RecvTimeout, ConnectionError, OSError):
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.5)
        params = unflatten_params(spec, jnp.asarray(initial))
    opt_state = optimizer.init(params)
    last_pull = np.asarray(flatten_params(params)[0])
    # training-dynamics plane: armed iff the transport is obs-wrapped —
    # the same zero-cost-when-off contract as the spans above. prev_center
    # remembers the previously fetched center for the fetch-delta norm.
    dyn_on = getattr(client.transport, "obs_tracer", None) is not None
    prev_center: Optional[np.ndarray] = None
    losses: list[float] = []
    pending: list = []
    consecutive_failures = 0
    skipped_rounds = 0
    total_failures = 0

    def flush():
        if pending:
            losses.extend(np.asarray(jnp.stack(pending)).tolist())
            pending.clear()

    done = 0
    round_no = 0
    while done < steps:
        k = min(tau, steps - done)
        t_c = time.perf_counter()
        with obs_span(
            client.transport, "compute", round=round_no + 1, steps=k
        ) as cspan:
            for _ in range(k):
                idx = rng.integers(0, len(x), batch_size)
                params, opt_state, loss = local_step(
                    params, opt_state, x[idx], y[idx]
                )
                pending.append(loss)
            if cspan is not None:
                # span live → pay the sync so compute time is real
                force_completion(params, loss)
        reg.inc(M_STEPS, k)
        reg.inc(M_SAMPLES, k * batch_size)
        reg.inc(M_COMPUTE_S, time.perf_counter() - t_c)
        done += k
        if k < tau:
            break  # steps % tau remainder trains without an exchange
        round_no += 1
        flush()
        # zero-copy wire contract (docs/WIRE.md): the framed transport
        # sends slices of this vector by reference (no serialize copy),
        # and PClient's blocking sends return only once written — so the
        # loop below must never mutate `flat` in place; the post-exchange
        # elastic move builds a NEW array.
        flat = np.asarray(flatten_params(params)[0])
        t_x = time.perf_counter()
        with obs_span(
            client.transport, "exchange",
            round=round_no, algo=algo,
        ):
            try:
                if algo == "easgd":
                    # fetch BEFORE push so the client's elastic move uses
                    # the pre-push center — the paper's update order (both
                    # moves on the old center), and the same order
                    # goptim.easgd_round implements for the collective
                    # path. Push-then-fetch would couple against a center
                    # already moved by this client's own push (an
                    # alpha*(1-alpha) effective move).
                    # The local params ride along as the repair fallback
                    # (ring mode): a dead server's shards are rerouted
                    # and THIS round's gap filled locally instead of
                    # skipping the round (docs/ROBUSTNESS.md).
                    center = client.fetch(fallback=flat)
                    client.push_easgd(flat)
                    if dyn_on:
                        _record_dynamics(
                            client.transport, reg, round_no, algo,
                            flat, center, prev_center, alpha=alpha,
                        )
                        prev_center = center
                    flat = flat - alpha * (flat - center)
                else:
                    delta = flat - last_pull
                    client.push_delta(delta)
                    # the pushed delta now belongs to the server: a fetch
                    # failure below must not get it re-pushed next round
                    prev_pull = last_pull
                    last_pull = flat
                    fetched = client.fetch(fallback=flat)
                    if dyn_on:
                        # elastic here = ‖local − fetched center‖; the
                        # fetch-delta baseline is the previous pull
                        _record_dynamics(
                            client.transport, reg, round_no, algo,
                            flat, fetched, prev_pull, push_vec=delta,
                        )
                    flat = fetched
                    last_pull = flat
            except (RecvTimeout, ConnectionError, OSError) as e:
                total_failures += 1
                consecutive_failures += 1
                reg.inc(M_EXCHANGE_FAILURES)
                if max_exchange_failures is None:
                    raise  # fail-fast semantics (degradation not enabled)
                if consecutive_failures >= max_exchange_failures:
                    raise RuntimeError(
                        f"PS exchange failed {consecutive_failures} "
                        "rounds in a row — escalating instead of "
                        "training further against an unreachable center"
                    ) from e
                skipped_rounds += 1
                reg.inc(M_SKIPPED_ROUNDS)
                reg.inc(M_EXCHANGE_S, time.perf_counter() - t_x)
                logger.warning(
                    "PS exchange failed (%r); skipping round on the "
                    "stale center (%d consecutive failure(s))",
                    e,
                    consecutive_failures,
                )
                continue  # params stay local this round
            consecutive_failures = 0
            dt_x = time.perf_counter() - t_x
            reg.inc(M_ROUNDS)
            reg.inc(M_EXCHANGE_S, dt_x)
            reg.observe(M_EXCHANGE_LAT, dt_x)
            reg.set_gauge(M_PUSHES, sum(client.push_sent.values()))
            reg.set_gauge(M_STALE_PARAMS, client.stale_params_dropped)
            reg.set_gauge(
                M_REPAIRED_CHUNKS, getattr(client, "repaired_chunks", 0)
            )
            params = unflatten_params(spec, jnp.asarray(flat))
    flush()  # flush any remainder losses
    if exchange_stats is not None:
        exchange_stats["skipped_rounds"] = skipped_rounds
        exchange_stats["exchange_failures"] = total_failures
        exchange_stats["repaired_chunks"] = getattr(
            client, "repaired_chunks", 0
        )
        # where this client's parameters live (the caller's
        # jax.default_device, or device 0 when it set none)
        exchange_stats["device"] = str(
            next(iter(jax.tree.leaves(params)[0].devices()))
        )
    return losses
