"""ptest_proc — the MNIST PS example in the reference's literal shape:
one OS process per rank, launched like mpirun (SURVEY.md §3(a)):

    python -m mpit_tpu.launch -n 3 examples/ptest_proc.py --steps 100

Rank→role split happens here, exactly as the reference's ptest.lua did it
from its MPI rank: ranks [0, servers) are pservers, the rest pclients.
Messages ride :class:`mpit_tpu.transport.SocketTransport` (TCP), addresses
from ``MPIT_TRANSPORT_HOSTS`` (exported by the launcher; set it yourself
across real hosts). Initial model state: every rank builds identical
params from the shared seed — the deterministic-init equivalent of the
reference's rank-0-construct + bcast.

The protocol body is `mpit_tpu.parallel.ps_roles.client_train_loop` — the
same code the thread-mode AsyncPSTrainer runs, so both modes are
protocol-identical by construction.

Devices: an accelerator chip belongs to one process, so the ranks split the
host before any of them initializes a jax backend. Server ranks are
numpy-only and run on the CPU platform; client ``c`` claims the host's
``c``-th TPU chip alone (``TPU_VISIBLE_CHIPS``). A host with fewer chips than
clients cannot run this shape: the client without a chip exits non-zero
saying so, and the launcher takes the world down with it (never a hang) —
run the clients as threads instead (``examples/ptest.py --algo ps-easgd``).
With ``JAX_PLATFORMS=cpu``, or on a host without TPU device files, every
rank is a plain process on jax's default platform and nothing is claimed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _host_chips() -> int:
    """How many TPU chips this host exposes as device files (v5e:
    ``/dev/vfio/N``; earlier generations: ``/dev/accelN``). Counted
    without jax: whoever asks jax for the devices takes them all."""
    import glob

    return sum(
        len(glob.glob(pattern))
        for pattern in ("/dev/vfio/[0-9]*", "/dev/accel[0-9]*")
    )


def _claim_device(rank: int, num_servers: int) -> str:
    """Give this rank a device no other rank will touch. Must run before
    the first jax computation (the platform choice is sticky and libtpu
    reads the chip list once, when the backend initializes). Returns a
    description for the rank's first output line."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return "cpu (JAX_PLATFORMS=cpu)"
    chips = _host_chips()
    if not chips:
        return "jax default platform (no TPU device files on this host)"
    if rank < num_servers:
        from mpit_tpu.utils.vmesh import repin_platform

        repin_platform("cpu")
        return "cpu (pserver is numpy-only)"
    client = rank - num_servers
    if client >= chips:
        raise SystemExit(
            f"rank {rank}: pclient {client} has no chip — this host exposes "
            f"{chips} TPU chip(s) and a chip belongs to one process; use "
            "at most that many client ranks, run the clients as threads "
            "(examples/ptest.py --algo ps-easgd), or set JAX_PLATFORMS=cpu"
        )
    # TPU_VISIBLE_CHIPS indexes the chips libtpu enumerates (0-based,
    # whatever their device files are numbered: the one-chip v5e machine
    # exposes /dev/vfio/3 and its chip is index 0); the one-chip process
    # bounds keep libtpu from waiting for the host's other chips
    os.environ["TPU_VISIBLE_CHIPS"] = str(client)
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return f"TPU chip {client} of {chips}"


def main():
    from mpit_tpu.utils.config import TrainConfig

    cfg = TrainConfig.from_args(description=__doc__)
    try:
        rank = int(os.environ["MPIT_RANK"])
        world = int(os.environ["MPIT_WORLD_SIZE"])
    except KeyError:
        raise SystemExit(
            "MPIT_RANK/MPIT_WORLD_SIZE not set — run under "
            "`python -m mpit_tpu.launch -n N examples/ptest_proc.py ...`"
        )
    claimed = _claim_device(rank, cfg.servers)

    import jax

    device = jax.devices()[0]
    print(f"rank {rank}: {device.platform} device {device} [{claimed}]",
          flush=True)

    import jax.numpy as jnp
    import numpy as np
    import optax

    from mpit_tpu.data import load_mnist
    from mpit_tpu.data.datasets import shard_for_worker
    from mpit_tpu.models import get_model
    from mpit_tpu.obs import wrap_from_env, write_fault_log
    from mpit_tpu.parallel import ps_roles
    from mpit_tpu.parallel.pclient import PClient
    from mpit_tpu.parallel.pserver import PServer, partition_bounds
    from mpit_tpu.transport import (
        ChaosTransport,
        SocketTransport,
        config_from_env as chaos_config_from_env,
    )
    from mpit_tpu.utils.params import flatten_params, unflatten_params

    num_servers = cfg.servers
    num_clients = world - num_servers
    if num_clients < 1:
        raise SystemExit(
            f"world of {world} with {num_servers} servers leaves no clients"
        )
    alpha = cfg.alpha if cfg.alpha is not None else 0.9 / num_clients

    x_tr, y_tr, x_te, y_te = load_mnist(synthetic_train=cfg.train_size)
    model = get_model(cfg.model)
    opt = optax.sgd(cfg.lr, momentum=cfg.momentum)
    # identical init on every rank from the shared seed (≡ rank-0 + bcast)
    params0 = model.init(jax.random.key(cfg.seed), jnp.asarray(x_tr[:2]))[
        "params"
    ]
    flat0, spec = flatten_params(params0)
    flat0 = np.asarray(flat0, np.float32)

    # chaos opt-in (docs/ROBUSTNESS.md): MPIT_CHAOS_* knobs wrap the
    # socket in the fault injector — same contract as thread mode, but
    # each process has its own FaultLog (faults are recorded sender-side,
    # so the per-rank union is the whole schedule)
    # MPIT_CONNECT_RETRY_S: how long a refused outbound connection is
    # retried. The 30s default absorbs startup skew, but it also hides a
    # dead peer — the sharded soak leg shrinks it so a killed server is
    # *seen* to be dead (and its shards rerouted) instead of every send
    # quietly waiting out the window
    base = SocketTransport(
        rank, world,
        connect_retry_s=float(os.environ.get("MPIT_CONNECT_RETRY_S", "30")),
    )
    chaos_cfg = chaos_config_from_env()
    fault_log = None
    if chaos_cfg is not None:
        base = ChaosTransport(base, chaos_cfg)
        fault_log = base.log
    # observability opt-in (docs/OBSERVABILITY.md): with any MPIT_OBS_*
    # knob set the transport is wrapped for tracing/telemetry — e.g.
    # MPIT_OBS_DIR=/tmp/run writes per-rank journals that
    # `python -m mpit_tpu.obs merge /tmp/run` turns into one Perfetto
    # timeline. Unset, this is the identity function. Telemetry wraps
    # OUTERMOST over chaos so its stream index stays in lockstep with
    # the chaos schedule (the fault-overlay join key).
    tp = wrap_from_env(base)
    if fault_log is not None:
        # ride the chaos schedule along every black-box dump: the
        # post-mortem then sees the injected faults inside the same
        # file as the final exchange rounds they explain
        from mpit_tpu.obs import box_for

        box = box_for(tp)
        if box is not None:
            box.add_source(
                "faults",
                lambda: [
                    {
                        "ev": "fault", "kind": e.kind, "src": e.src,
                        "dst": e.dst, "tag": e.tag, "n": e.n,
                    }
                    for e in fault_log.events()
                ],
            )
    server_ranks = list(range(num_servers))
    client_ranks = list(range(num_servers, world))
    bounds = partition_bounds(flat0.size, num_servers)

    # sharded ownership opt-in (docs/ROBUSTNESS.md "Shard ownership &
    # resharding"): MPIT_PS_SHARDS=N splits the flat vector into N ring-
    # placed shards so clients reassign a killed server's shards to the
    # survivors (live resharding) instead of skipping its range forever
    ps_shards = int(os.environ.get("MPIT_PS_SHARDS", "0"))
    shard_map = None
    if ps_shards > 0:
        from mpit_tpu.comm.topology import HashRing, ShardMap

        shard_map = ShardMap(HashRing(server_ranks), flat0.size, ps_shards)

    # elastic mode (docs/ROBUSTNESS.md): set by the supervising launcher
    # (MPIT_ELASTIC_RESPAWN=1) — clients announce themselves with JOIN so
    # a respawned replacement registers a fresh dedup epoch, servers
    # snapshot their shard for kill→restore recovery, and exchange
    # failures degrade to skipped rounds instead of killing the run.
    elastic = os.environ.get("MPIT_ELASTIC_RESPAWN", "0") not in ("", "0")
    ckpt_dir = os.environ.get("MPIT_ELASTIC_CKPT_DIR")
    # elastic implies the dead-client watchdog: a restored server whose
    # snapshot predates some client's STOP would otherwise wait forever
    # for a rank that already exited cleanly and will never speak again
    client_timeout = cfg.client_timeout
    if client_timeout is None and elastic:
        client_timeout = 15.0

    if rank < num_servers:
        start, end = bounds[rank]
        if shard_map is not None:
            pieces = [flat0[s:e] for _, s, e in shard_map.ranges_for(rank)]
            center0 = (
                np.concatenate(pieces) if pieces else np.zeros(0, np.float32)
            )
        else:
            center0 = flat0[start:end]
        server = PServer(
            tp, center0,
            num_clients=num_clients, alpha=alpha,
            client_ranks=client_ranks,
            client_timeout=client_timeout,
            ckpt_path=(
                os.path.join(ckpt_dir, f"shard_{rank}.msgpack")
                if ckpt_dir else None
            ),
            ckpt_every=int(os.environ.get("MPIT_ELASTIC_CKPT_EVERY", "5")),
            shard_map=shard_map,
        )
        server.start()  # blocks until every client stopped (or died)
        print(
            f"pserver rank {rank}: counts={server.counts} "
            f"dead_clients={sorted(server.dead_clients)}"
        )
    else:
        c = rank - num_servers
        hb = client_timeout / 3 if client_timeout else None
        client = PClient(
            tp, server_ranks, flat0.size, heartbeat_interval=hb,
            # elastic: a killed server respawns within seconds — waiting
            # the default 60s per attempt would stall its clients past
            # the soak budget; short attempts + skipped rounds instead.
            # The sharded soak leg overrides both knobs so a killed
            # server is declared dead (and its shards rerouted) within
            # seconds, not after the full retry ladder
            timeout=float(
                os.environ.get("MPIT_PS_TIMEOUT")
                or (15.0 if elastic else 60.0)
            ),
            max_retries=int(os.environ.get("MPIT_PS_MAX_RETRIES", "3")),
            shard_map=shard_map,
        )
        xs = shard_for_worker(x_tr, c, num_clients)
        ys = shard_for_worker(y_tr, c, num_clients)
        local_step = ps_roles.make_local_step(model, opt)
        per_client = max(cfg.global_batch // num_clients, 1)
        losses = ps_roles.client_train_loop(
            client, local_step, opt, spec, xs, ys,
            steps=cfg.steps, batch_size=per_client, tau=cfg.tau,
            algo=cfg.resolved_algo().removeprefix("ps-")
            if cfg.algo.startswith("ps-") else "easgd",
            alpha=alpha, seed=cfg.seed + 1000 + c,
            join=elastic,
            max_exchange_failures=8 if elastic else None,
        )
        if c == 0:
            # final center fetch BEFORE stop (servers still serving)
            center = unflatten_params(spec, jnp.asarray(client.fetch()))
            apply = jax.jit(
                lambda p, xb: model.apply({"params": p}, xb)
            )
            correct = 0
            n = (len(x_te) // 512) * 512 or len(x_te)
            for i in range(0, n, 512):
                logits = apply(center, x_te[i : i + 512])
                correct += int(
                    np.sum(np.argmax(logits, -1) == y_te[i : i + 512])
                )
            print(
                f"pclient 0: test acc={correct / n:.4f} "
                f"final loss={losses[-1]:.4f}"
            )
        client.stop()
    obs_dir = os.environ.get("MPIT_OBS_DIR")
    if fault_log is not None and obs_dir:
        # per-rank fault log for the merger's --faults overlay (a
        # directory of faults_rank*.jsonl is accepted there)
        write_fault_log(
            fault_log.events(),
            os.path.join(obs_dir, f"faults_rank{rank}.jsonl"),
        )
    tp.close()


if __name__ == "__main__":
    main()
