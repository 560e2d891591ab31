"""Host-async parameter-server trainer: genuine protocol asynchrony.

This is fidelity mode (SURVEY.md §5 backend mapping, item (ii)): the
collective EASGD/Downpour trainers are the fast path (everything fused under
jit over ICI), while this trainer preserves the reference's *runtime
structure* — concurrent pserver/pclient actors exchanging tagged messages
with real interleaving and unbounded staleness (BASELINE.json:7's
"2 pclient + 1 pserver" shape). Clients run their τ local steps as
jit-compiled XLA programs (one jitted function shared by all client
threads — same shapes, one compile per device it runs on; the GIL is
released inside XLA so clients genuinely overlap), each client thread on
its own device where the process has several, and only flat numpy vectors
cross the transport.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mpit_tpu.data.datasets import shard_for_worker
from mpit_tpu.obs.core import (
    ObsConfig,
    arm_faulthandler,
    disarm_faulthandler,
    write_fault_log,
)
from mpit_tpu.obs.core import config_from_env as obs_config_from_env
from mpit_tpu.obs.telemetry import wrap_obs_transports
from mpit_tpu.parallel import common, ps_roles
from mpit_tpu.parallel.pclient import PClient
from mpit_tpu.parallel.pserver import PServer, partition_bounds, spawn_server_thread
from mpit_tpu.transport import Broker
from mpit_tpu.transport.chaos import (
    ChaosConfig,
    FaultLog,
    config_from_env,
    wrap_transports,
)
from mpit_tpu.utils.params import flatten_params, unflatten_params


def _chaos_counts(fault_log: FaultLog, rank: int) -> Callable[[], dict]:
    """Live-snapshot collector: this rank's injected-fault counts by kind
    (faults are attributed to the rank whose send the injector hit)."""

    def counts() -> dict:
        out: dict = {}
        for e in fault_log.events():
            if e.src == rank:
                out[e.kind] = out.get(e.kind, 0) + 1
        return out

    return counts


class AsyncPSTrainer:
    """2-pclient+1-pserver-style async training (counts configurable).

    Transport ranks: ``[0, num_servers)`` are pservers, the rest pclients.

    Args:
      algo: "easgd" (push params, elastic moves on both sides) or
        "downpour" (push accumulated delta, pull-replace).
      alpha: elastic coupling (both server- and client-side move).
      tau: local steps between exchanges.
      transport: "native" (C++ broker, ``mpit_tpu.native``), "inproc"
        (pure-Python broker), "socket" (real TCP loopback: every actor gets
        its own :class:`SocketTransport` on an ephemeral port — actors are
        still threads, but every message crosses a genuine socket with the
        framed wire codec, so the serialize/transfer/deserialize phase
        split and exact byte counters are real; the bench's wire-format
        A/B mode), or "auto" (native when buildable — it is the
        reference-parity message plane, SURVEY.md §2 comp. 1). Tradeoff:
        inproc passes payload *references* (zero copies, fastest per-message
        for huge payloads), native moves real bytes (~memcpy bandwidth) but
        blocks receivers fully off the GIL; end-to-end MNIST PS training
        with 4 clients measured ~17% faster on native. For very large flat
        vectors (ResNet-50-scale) prefer "inproc".
      ckpt_dir: elastic recovery (SURVEY.md §5 do-better over the
        reference's lose-everything semantics): each server persists its
        center chunk to ``ckpt_dir/center_<rank>.npy`` every
        ``ckpt_every`` updates and at teardown; with ``resume`` (the
        default) a fresh ``train()`` whose servers find matching chunks
        restores the center — a killed-and-restarted job continues from
        the last persisted center instead of re-initializing. ``resume=
        False`` deletes stale chunks first (a deliberate fresh start).
        Client rejoin needs no persistence: a replacement client on a
        dead client's rank fetches the live center and its first message
        revives it at the server watchdog (tests/test_failure.py).
      chaos: fault-injection schedule (docs/ROBUSTNESS.md). When set —
        or when any ``MPIT_CHAOS_*`` env knob is — every transport is
        wrapped in a :class:`ChaosTransport` sharing one fault log
        (``stats["chaos_faults"]``); the run must then survive on the
        retry/dedup/degradation machinery below.
      obs: observability config (docs/OBSERVABILITY.md). When set — or
        when any ``MPIT_OBS_*`` env knob is — every transport is wrapped
        in a :class:`~mpit_tpu.obs.telemetry.TelemetryTransport`
        OUTERMOST (over chaos, so telemetry stream indices stay in
        lockstep with the fault schedule's): per-(peer, tag) wire
        counters land in ``stats["telemetry"]``, per-rank journals under
        ``obs.dir`` feed ``python -m mpit_tpu.obs merge``, and when
        chaos is also active the fault log is persisted next to them as
        ``faults.jsonl`` for the timeline overlay. Unset, no wrapper
        exists at all — the measured-zero-overhead contract.
      max_exchange_failures: graceful degradation — a client's failed
        exchange (after PClient's own retries) skips the round on the
        stale center; this many CONSECUTIVE failures escalate to an
        error. ``None`` = fail on the first exchange error.
      fetch_timeout / fetch_retries: forwarded to each PClient — the
        per-attempt PARAM wait and the retry budget for FETCH/PARAM
        and push sends. Chaos tests drop these to sub-second values so
        injected losses resolve quickly.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        num_clients: int = 2,
        num_servers: int = 1,
        algo: str = "easgd",
        alpha: float = 0.5,
        tau: int = 4,
        server_lr: float = 1.0,
        loss_fn: Optional[Callable] = None,
        transport: str = "auto",
        client_timeout: Optional[float] = None,
        ckpt_dir: Optional[str] = None,
        ckpt_every: Optional[int] = 100,
        resume: bool = True,
        chaos: Optional[ChaosConfig] = None,
        obs: Optional[ObsConfig] = None,
        max_exchange_failures: Optional[int] = 3,
        fetch_timeout: float = 60.0,
        fetch_retries: int = 3,
        ps_shards: Optional[int] = None,
    ):
        if algo not in ("easgd", "downpour"):
            raise ValueError(f"unknown algo {algo!r}")
        if transport not in ("auto", "native", "inproc", "socket"):
            raise ValueError(f"unknown transport {transport!r}")
        self.transport_kind = transport
        # failure detection (SURVEY.md §5 do-better): silence beyond this →
        # the client is declared dead instead of hanging the job forever
        if client_timeout is not None and client_timeout <= 0:
            raise ValueError(
                "client_timeout must be positive (use None to disable)"
            )
        self.client_timeout = client_timeout
        if num_clients < 1 or num_servers < 1:
            raise ValueError("need at least one client and one server")
        self.model = model
        self.optimizer = optimizer
        self.num_clients = num_clients
        self.num_servers = num_servers
        self.algo = algo
        self.alpha = float(alpha)
        self.tau = int(tau)
        self.server_lr = float(server_lr)
        self.loss_fn = (
            loss_fn if loss_fn is not None else common.default_loss_fn(model.apply)
        )
        if ckpt_every is not None and ckpt_every < 1:
            raise ValueError(
                "ckpt_every must be >= 1 (None = persist only at teardown)"
            )
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = None if ckpt_every is None else int(ckpt_every)
        self.resume = bool(resume)
        if max_exchange_failures is not None and max_exchange_failures < 1:
            raise ValueError(
                "max_exchange_failures must be >= 1 (None = fail fast)"
            )
        if fetch_timeout <= 0:
            raise ValueError("fetch_timeout must be positive")
        if fetch_retries < 0:
            raise ValueError("fetch_retries must be >= 0")
        self.chaos = chaos
        self.obs = obs
        # sharded ownership (docs/ROBUSTNESS.md "Shard ownership &
        # resharding"): split the flat vector into this many shards placed
        # on servers by a consistent-hash ring, so clients can reassign a
        # dead server's shards to the survivors mid-run (live resharding)
        # instead of degrading every round that touches its range. None
        # (the default) keeps the legacy one-contiguous-chunk-per-server
        # layout. Env opt-in MPIT_PS_SHARDS serves launcher-driven runs.
        if ps_shards is None:
            import os

            env_shards = int(os.environ.get("MPIT_PS_SHARDS", "0"))
            ps_shards = env_shards if env_shards > 0 else None
        if ps_shards is not None and ps_shards < 1:
            raise ValueError("ps_shards must be >= 1 (None = legacy layout)")
        self.ps_shards = ps_shards
        self.max_exchange_failures = max_exchange_failures
        self.fetch_timeout = float(fetch_timeout)
        self.fetch_retries = int(fetch_retries)
        self.fault_log: Optional[FaultLog] = None
        # one jitted local step shared by all client threads (same shapes,
        # one compile per device; XLA releases the GIL so clients overlap)
        self._local_step = ps_roles.make_local_step(
            model, optimizer, self.loss_fn
        )

    def _make_broker(self, size: int):
        if self.transport_kind in ("auto", "native"):
            import mpit_tpu.native as native

            if native.is_available():
                return native.NativeBroker(size)
            if self.transport_kind == "native":
                # surface WHY it is unavailable (explicit request must never
                # silently substitute the Python broker)
                native.ensure_built()
                return native.NativeBroker(size)
        return Broker(size)

    def _make_transports(self, size: int) -> list:
        if self.transport_kind != "socket":
            return self._make_broker(size).transports()
        # real-TCP loopback world: reserve one ephemeral port per rank
        # (bind 0, read, release), then hand every rank the full address
        # table. The release→bind window is racy in principle; in practice
        # the kernel avoids handing a just-released ephemeral port straight
        # back out, and a lost race fails loudly at bind.
        import socket as _socket

        from mpit_tpu.transport.socket_transport import SocketTransport

        probes = []
        addrs: list[tuple[str, int]] = []
        for _ in range(size):
            s = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
            s.bind(("127.0.0.1", 0))
            addrs.append(("127.0.0.1", s.getsockname()[1]))
            probes.append(s)
        for s in probes:
            s.close()
        return [
            SocketTransport(r, size, addresses=addrs) for r in range(size)
        ]

    def train(
        self,
        x: np.ndarray,
        y: np.ndarray,
        steps: int,
        batch_size: int = 64,
        init_rng=None,
        seed: int = 0,
    ):
        """Run the async job; returns (center_params, stats).

        Each client trains on its own contiguous data shard (per-rank split,
        as the reference sharded MNIST by worker id) for ``steps`` local
        steps, exchanging with the servers every ``tau`` steps.
        """
        init_rng = init_rng if init_rng is not None else jax.random.key(seed)
        params0 = self.model.init(init_rng, jnp.asarray(x[:2]))["params"]
        flat0, spec = flatten_params(params0)
        flat0 = np.asarray(flat0, np.float32)

        raw_transports = self._make_transports(
            self.num_servers + self.num_clients
        )
        transports = raw_transports
        # fault injection: explicit config wins, env knobs activate it for
        # launcher-driven runs (MPIT_CHAOS_*; see launch.py's diagnostic)
        chaos_cfg = self.chaos if self.chaos is not None else config_from_env()
        self.fault_log = None
        if chaos_cfg is not None:
            transports, self.fault_log = wrap_transports(transports, chaos_cfg)
        # observability wraps OUTERMOST over chaos: counters see every
        # attempted send (faults included), latency includes injected
        # delay, and the per-(dst, tag) stream index stays in lockstep
        # with the chaos schedule's — the merger's fault-placement key
        obs_cfg = self.obs if self.obs is not None else obs_config_from_env()
        obs_transports: list = []
        if obs_cfg is not None:
            # hung-job forensics (MPIT_OBS_FAULTHANDLER): periodic all-thread
            # stack dumps while the job runs, cancelled at clean teardown
            arm_faulthandler(obs_cfg, "trainer")
            transports = wrap_obs_transports(transports, obs_cfg)
            obs_transports = transports
            if obs_cfg.live and self.fault_log is not None:
                # per-rank chaos fault counts ride the live snapshots: a
                # pull collector sampled at export time (the FaultLog is
                # already thread-safe; no hot-path cost)
                for t in obs_transports:
                    t.obs_registry.add_collector(
                        "chaos", _chaos_counts(self.fault_log, t.rank)
                    )
        server_ranks = list(range(self.num_servers))
        client_ranks = list(
            range(self.num_servers, self.num_servers + self.num_clients)
        )
        bounds = partition_bounds(flat0.size, self.num_servers)
        shard_map = None
        if self.ps_shards is not None:
            from mpit_tpu.comm.topology import HashRing, ShardMap

            # ring placement: every actor derives the same shard→server
            # assignment from the member list alone (blake2b, not Python
            # hash()), so no coordinator hands out the layout
            shard_map = ShardMap(
                HashRing(server_ranks), flat0.size, self.ps_shards
            )

        ckpt_paths = [None] * self.num_servers
        if self.ckpt_dir is not None:
            import os

            os.makedirs(self.ckpt_dir, exist_ok=True)
            ckpt_paths = [
                os.path.join(self.ckpt_dir, f"center_{r}.npy")
                for r in server_ranks
            ]
            if not self.resume:  # deliberate fresh start: drop stale chunks
                for p in ckpt_paths:
                    if os.path.exists(p):
                        os.remove(p)
        def _server_center(r: int, start: int, end: int) -> np.ndarray:
            if shard_map is None:
                return flat0[start:end]
            # sharded: this server's center is the ascending concat of the
            # shards the ring assigns it (possibly non-contiguous in the
            # flat vector, possibly empty when servers outnumber shards)
            pieces = [flat0[s:e] for _, s, e in shard_map.ranges_for(r)]
            if not pieces:
                return np.zeros(0, np.float32)
            return np.concatenate(pieces)

        servers = [
            PServer(
                transports[r],
                _server_center(r, start, end),
                num_clients=self.num_clients,
                alpha=self.alpha,
                server_lr=self.server_lr,
                client_ranks=client_ranks,
                client_timeout=self.client_timeout,
                ckpt_path=path,
                ckpt_every=self.ckpt_every,
                shard_map=shard_map,
            )
            for r, (start, end), path in zip(server_ranks, bounds, ckpt_paths)
        ]
        server_threads = [spawn_server_thread(s) for s in servers]

        losses = [[] for _ in range(self.num_clients)]
        errors: list[BaseException] = []
        clients: list = [None] * self.num_clients
        exchange_stats: list[dict] = [{} for _ in range(self.num_clients)]
        # one device per client thread, round-robin over this process's
        # devices: without it every client's arrays land on device 0 and
        # the other chips of the host idle (default_device is per-thread)
        local_devices = jax.local_devices()

        def client_main(c: int):
            client = None
            try:
                tp = transports[self.num_servers + c]
                hb = (
                    self.client_timeout / 3
                    if self.client_timeout is not None
                    else None
                )
                client = PClient(
                    tp, server_ranks, flat0.size, heartbeat_interval=hb,
                    timeout=self.fetch_timeout,
                    max_retries=self.fetch_retries,
                    shard_map=shard_map,
                )
                clients[c] = client
                xs = shard_for_worker(x, c, self.num_clients)
                ys = shard_for_worker(y, c, self.num_clients)
                with jax.default_device(
                    local_devices[c % len(local_devices)]
                ):
                    losses[c] = ps_roles.client_train_loop(
                        client, self._local_step, self.optimizer, spec,
                        xs, ys, steps, batch_size, self.tau, self.algo,
                        self.alpha, seed=seed + 1000 + c,
                        max_exchange_failures=self.max_exchange_failures,
                        exchange_stats=exchange_stats[c],
                    )
                client.stop()
            except BaseException as e:  # surface thread failures to caller
                errors.append(e)
                try:
                    if client is not None:
                        # stops the heartbeat thread AND detaches — a leaked
                        # heartbeat would flood the brokers forever
                        client.stop()
                    else:
                        PClient(
                            transports[self.num_servers + c],
                            server_ranks,
                            flat0.size,
                        ).stop()
                except Exception:
                    pass

        client_threads = [
            threading.Thread(target=client_main, args=(c,), daemon=True)
            for c in range(self.num_clients)
        ]
        def teardown_transports():
            # socket mode owns real OS resources (listeners, connections,
            # sender threads) — close them; broker modes die with the run
            if self.transport_kind == "socket":
                for t in raw_transports:
                    try:
                        t.close()
                    except OSError:
                        pass

        for t in client_threads:
            t.start()
        for t in client_threads:
            t.join()
        for t in server_threads:
            t.join(timeout=30)
        server_errors = [s.error for s in servers if s.error is not None]
        if server_errors:
            teardown_transports()
            raise RuntimeError("pserver died during training") from server_errors[0]
        if errors:
            teardown_transports()
            raise errors[0]

        if shard_map is None:
            center_flat = np.concatenate([s.snapshot() for s in servers])
        else:
            # place each server's owned shards back by the STATIC layout
            # (ownership may have moved mid-run; seed values back any shard
            # nobody ended up holding)
            center_flat = np.array(flat0, copy=True)
            for s in servers:
                snap = s.snapshot()
                off = 0
                for _sid, start, end in s.owned_ranges():
                    n = end - start
                    center_flat[start:end] = snap[off:off + n]
                    off += n
        center_params = unflatten_params(spec, jnp.asarray(center_flat))
        stats = {
            # the message plane that actually ran — "auto" resolves to the
            # native broker only where its library builds or is prebuilt
            "transport": type(raw_transports[0]).__name__,
            "client_devices": [s.get("device") for s in exchange_stats],
            "server_counts": [dict(s.counts) for s in servers],
            # True iff every server restored a persisted center chunk —
            # the elastic-recovery signal a resumed job asserts on
            "center_restored": all(s.restored for s in servers),
            # reported as client INDICES (0..num_clients), consistent with
            # "losses" and data sharding — not raw transport ranks
            "dead_clients": sorted(
                r - self.num_servers
                for r in set().union(*(s.dead_clients for s in servers))
            ),
            "mean_final_loss": float(
                np.mean([l[-1] for l in losses if l]) if any(losses) else np.nan
            ),
            "losses": losses,
            # robustness accounting (docs/ROBUSTNESS.md): per-client push
            # sends that reached the transport (== what servers should
            # have applied under dedup), rounds degraded, stale PARAM
            # replies the attempt-id check discarded
            "push_sent": [
                dict(c.push_sent) if c is not None else {} for c in clients
            ],
            "stale_params_dropped": [
                c.stale_params_dropped if c is not None else 0
                for c in clients
            ],
            "skipped_rounds": [
                s.get("skipped_rounds", 0) for s in exchange_stats
            ],
            # sharded repair accounting: per-client count of shards the
            # client re-routed to surviving owners after a server death
            # (0s in legacy mode; see docs/ROBUSTNESS.md)
            "ps_shards": self.ps_shards,
            "repaired_chunks": [
                s.get("repaired_chunks", 0) for s in exchange_stats
            ],
            "exchange_failures": [
                s.get("exchange_failures", 0) for s in exchange_stats
            ],
            # dynamics plane (docs/OBSERVABILITY.md "dynamics"): per-server
            # center version reached, and per-source push-staleness tallies
            # (center updates applied between a client's fetch basis and
            # its push landing) — the in-memory twin of the journal's
            # push_stale records
            "server_versions": [s.version for s in servers],
            "staleness_by_src": [
                {src: dict(st) for src, st in sorted(
                    s.staleness_by_src.items())}
                for s in servers
            ],
        }
        if self.fault_log is not None:
            stats["chaos_faults"] = self.fault_log.counts()
        if obs_transports:
            stats["telemetry"] = [t.summary() for t in obs_transports]
            if obs_cfg.dir is not None and self.fault_log is not None:
                import os

                write_fault_log(
                    self.fault_log.events(),
                    os.path.join(obs_cfg.dir, "faults.jsonl"),
                )
            for t in obs_transports:
                # flush/close journals now — the broker dies with this
                # call, and a merge may run immediately after train()
                t.obs_tracer.close()
                # stop live exporters too (final snapshot hits disk)
                t.close_live()
            if obs_cfg.faulthandler > 0:
                disarm_faulthandler()
        # exact socket-level byte totals (socket mode only): ground truth
        # next to the telemetry summaries' per-(peer,tag) byte counters
        if self.transport_kind == "socket":
            stats["wire_bytes"] = [
                t.wire_byte_counts() for t in raw_transports
            ]
        teardown_transports()
        return center_params, stats

    def evaluate(self, params, x, y, batch: int = 512) -> float:
        apply = jax.jit(lambda p, xb: self.model.apply({"params": p}, xb))
        correct = 0
        n = (len(x) // batch) * batch or len(x)
        for i in range(0, n, batch):
            logits = apply(params, x[i : i + batch])
            correct += int(np.sum(np.argmax(logits, -1) == y[i : i + batch]))
        return correct / n
