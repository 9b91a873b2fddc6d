"""python -m paddle_tpu.distributed.launch (reference:
python/paddle/distributed/launch/main.py:23; CollectiveController.build_pod
launch/controllers/collective.py:37,262; restart policy --max_restart;
elastic relaunch fleet/elastic/manager.py:457-530).

TPU-native process model: ONE process per host (jax owns all local chips);
--nproc_per_node>1 supported for the CPU-backend test mode. Env contract
matches the reference (PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM,
PADDLE_TRAINER_ENDPOINTS, PADDLE_MASTER).

Multi-node rendezvous (real, not fabricated): the launcher whose bind on
the --master port wins hosts the TCPStore master daemon; every node
(auto-)assigns its node rank via store ADD, publishes its *real* worker
endpoints under ``launch/{job}/g{gen}/node/{rank}``, barriers on all
nodes, and builds the global rank/endpoint table from what was published —
the reference's master-KV build_pod flow over our own store.

Restart: a non-zero worker exit bumps the shared restart generation
(store ADD); every launcher polls the generation, kills its pod, and
re-runs rendezvous under the new generation, up to --max_restart times.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _free_port(host="127.0.0.1"):
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _advertise_ip(master_host: str) -> str:
    """The IP peers can reach us on: the one routing toward the master."""
    if master_host in ("127.0.0.1", "localhost"):
        return "127.0.0.1"
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect((master_host, 9))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return socket.gethostbyname(socket.gethostname())


class GenerationChanged(Exception):
    """A newer restart generation superseded the one being rendezvoused."""

    def __init__(self, gen: int):
        super().__init__(f"superseded by generation {gen}")
        self.gen = gen


class _Rendezvous:
    """Store-backed node rendezvous + restart-generation channel."""

    def __init__(self, master: str, nnodes: int, job_id: str,
                 node_rank: int, timeout: float = 900.0):
        from ..store import TCPStore

        host, port = master.rsplit(":", 1)
        self.job = job_id
        self.nnodes = nnodes
        self.timeout = timeout
        # the rank-0 contender hosts the daemon; everyone else connects.
        # With an explicit --rank we know who we are; with auto-assign the
        # machine that can bind the master address decides (binding the
        # master's concrete IP fails with EADDRNOTAVAIL on other hosts)
        is_master = node_rank == 0
        if node_rank < 0:
            try:
                probe = socket.socket()
                probe.bind((host if host != "localhost" else "127.0.0.1",
                            int(port)))
                probe.close()
                is_master = True
            except OSError:
                is_master = False
        try:
            self.store = TCPStore(host, int(port), is_master=is_master,
                                  world_size=nnodes, timeout=timeout)
        except OSError:
            # lost the probe->bind race to a same-host peer: be a client
            is_master = False
            self.store = TCPStore(host, int(port), is_master=False,
                                  world_size=nnodes, timeout=timeout)
        self.hosts_store = is_master
        if node_rank < 0:
            node_rank = self.store.add(f"launch/{self.job}/nodes", 1) - 1
        self.node_rank = node_rank

    def exchange_endpoints(self, gen: int, endpoints: list[str]) -> dict:
        """Publish our endpoints, wait for all nodes, return
        {node_rank: [endpoints]} (reference: build_pod master-KV sync).

        Waits in short slices and aborts with :class:`GenerationChanged`
        if the restart counter moves past ``gen`` — two nodes failing
        concurrently would otherwise rendezvous under different
        generations and deadlock until the full timeout."""
        key = f"launch/{self.job}/g{gen}/node/{self.node_rank}"
        self.store.set(key, json.dumps(endpoints).encode())
        peers = {}
        deadline = time.time() + self.timeout
        for r in range(self.nnodes):
            k = f"launch/{self.job}/g{gen}/node/{r}"
            while True:
                if self.store.check(k):
                    break
                cur = self.restart_gen()
                if cur > gen:
                    raise GenerationChanged(cur)
                if time.time() > deadline:
                    raise TimeoutError(
                        f"rendezvous g{gen}: node {r} never published")
                time.sleep(0.2)
            peers[r] = json.loads(self.store.get(k).decode())
        return peers

    def mark_done(self, gen: int) -> int:
        """Count this node's workers as finished for generation ``gen``
        (generation-scoped so a restart starts the count afresh)."""
        return self.store.add(f"launch/{self.job}/g{gen}/done", 1)

    def finish_done_count(self, gen: int) -> int:
        return self.store.add(f"launch/{self.job}/g{gen}/done", 0)

    def leave(self, gen: int, grace: float = 10.0) -> None:
        """Last store op of a finished job. The store dies with the node
        that hosts it, so the host leaves last: every other node counts
        itself out and touches the store no more; the host waits (bounded)
        for that count. Without this a host that exits quickly closes the
        store under a peer's next poll, and the peer fails a job that
        succeeded."""
        key = f"launch/{self.job}/g{gen}/left"
        if not self.hosts_store:
            self.store.add(key, 1)
            return
        deadline = time.time() + grace
        while self.store.add(key, 0) < self.nnodes - 1 and \
                time.time() < deadline:
            time.sleep(0.05)

    def restart_gen(self) -> int:
        return self.store.add(f"launch/{self.job}/restart", 0)

    def bump_restart(self) -> int:
        return self.store.add(f"launch/{self.job}/restart", 1)

    def regenerate(self, gen: int):
        """Re-register for a restart generation: fresh contiguous node
        ranks (a dead node leaves no hole) and a possibly-scaled node
        count (reference: elastic manager scale-in/out :484-530). Returns
        (gen_rank, gen_nnodes); gen_rank >= gen_nnodes means this node
        was scaled in and should exit."""
        nnodes = self.nnodes
        if self.store.check("elastic/num_nodes"):
            nnodes = int(self.store.get("elastic/num_nodes").decode())
        rank = self.store.add(f"launch/{self.job}/g{gen}/nodes", 1) - 1
        self.node_rank = rank
        self.nnodes = nnodes
        return rank, nnodes


def _spawn_pod(args, node_rank, nproc, world, rank_base, master, endpoints,
               gen):
    """Start this node's worker processes with the launch env contract."""
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    extra_path = pkg_root + (os.pathsep + os.environ["PYTHONPATH"]
                             if os.environ.get("PYTHONPATH") else "")
    procs = []
    os.makedirs(args.log_dir, exist_ok=True)
    for local_rank in range(nproc):
        rank = rank_base + local_rank
        env = dict(os.environ)
        env["PYTHONPATH"] = extra_path
        # workers are CPU orchestration (gloo collectives, stores, rpc):
        # pin each one to the CPU explicitly. Left to the default, every
        # worker on a TPU host would try to claim ALL of its chips, and
        # a chip belongs to one process. A job on chips is one process
        # driving all of them (TrainStep(mesh=...)).
        env["JAX_PLATFORMS"] = "cpu"
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_MASTER": master,
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_NODE_RANK": str(node_rank),
            "PADDLE_RESTART_GEN": str(gen),
            "FLAGS_selected_devices": str(local_rank),
        })
        if args.store_hosted:
            env["PADDLE_STORE_HOSTED"] = "1"
        if args.backend:
            env["PADDLE_DIST_BACKEND"] = args.backend
        log_file = os.path.join(args.log_dir, f"workerlog.{rank}")
        with open(log_file, "ab") as lf:
            p = subprocess.Popen(
                [sys.executable, args.training_script]
                + args.training_script_args,
                env=env, stdout=lf if world > 1 else None,
                stderr=subprocess.STDOUT if world > 1 else None)
        procs.append(p)
    return procs


def _kill_pod(procs):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + 10
    for p in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()


def launch(argv=None):
    parser = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch distributed training (reference: "
                    "python -m paddle.distributed.launch)")
    parser.add_argument("--nnodes", type=str, default="1")
    parser.add_argument("--nproc_per_node", type=int, default=None)
    parser.add_argument("--master", type=str, default=None)
    parser.add_argument("--rank", type=int, default=-1)
    parser.add_argument("--run_mode", type=str, default="collective")
    parser.add_argument("--job_id", type=str, default="default")
    parser.add_argument("--devices", "--gpus", type=str, default=None)
    parser.add_argument("--log_dir", type=str, default="log")
    parser.add_argument("--log_level", type=str, default="INFO")
    parser.add_argument("--max_restart", type=int, default=3)
    parser.add_argument("--rdv_timeout", type=float, default=900.0,
                        help="rendezvous/finish barrier wait (seconds)")
    parser.add_argument("--backend", type=str, default=None)
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if args.devices is not None:
        parser.error(
            "--devices/--gpus: launch workers are pinned to the CPU "
            "(JAX_PLATFORMS=cpu); a job on chips is ONE process over all "
            "of them, not one worker per chip")

    nnodes = int(str(args.nnodes).split(":")[0])
    nproc = args.nproc_per_node or 1

    multi_node = nnodes > 1 or args.master is not None
    args.store_hosted = multi_node
    rdv = None
    if multi_node:
        master = args.master or f"127.0.0.1:{_free_port()}"
        rdv = _Rendezvous(master, nnodes, args.job_id, args.rank,
                          timeout=args.rdv_timeout)
        node_rank = rdv.node_rank
    else:
        master = args.master or f"127.0.0.1:{_free_port()}"
        node_rank = max(args.rank, 0)

    world = nnodes * nproc
    procs: list = []
    current_gen = rdv.restart_gen() if rdv else 0
    restarts_used = 0

    def _build_and_spawn(gen):
        if rdv is not None:
            if gen > 0:
                # restart generation: re-register for fresh contiguous
                # node ranks + possibly-scaled node count (dead/scaled-in
                # nodes leave no hole in the new rendezvous)
                gen_rank, gen_nnodes = rdv.regenerate(gen)
                if gen_rank >= gen_nnodes:
                    _kill_pod(procs)
                    sys.exit(0)  # scaled in
            ip = _advertise_ip(master.rsplit(":", 1)[0])
            mine = [f"{ip}:{_free_port()}" for _ in range(nproc)]
            peers = rdv.exchange_endpoints(gen, mine)
            ordered = [ep for r in sorted(peers) for ep in peers[r]]
            endpoints = ",".join(ordered)
            gen_world = len(ordered)
            rank_base = sum(len(peers[r]) for r in sorted(peers)
                            if r < rdv.node_rank)
            return _spawn_pod(args, rdv.node_rank, nproc, gen_world,
                              rank_base, master, endpoints, gen)
        endpoints = ",".join(
            f"127.0.0.1:{_free_port()}" for _ in range(world))
        rank_base = node_rank * nproc
        return _spawn_pod(args, node_rank, nproc, world, rank_base, master,
                          endpoints, gen)

    def _spawn_gen(gen):
        """Rendezvous+spawn, following generation bumps that land while we
        wait (one logical fault = one restart, however many nodes bump)."""
        while True:
            try:
                return gen, _build_and_spawn(gen)
            except GenerationChanged as e:
                gen = e.gen

    current_gen, procs = _spawn_gen(current_gen)

    def _terminate(code=1, *_):
        _kill_pod(procs)
        sys.exit(code if isinstance(code, int) and code else 1)

    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)

    exit_code = 0
    local_done = False
    done_marked = False
    done_deadline = None
    try:
        while True:
            time.sleep(0.2)
            # cross-node restart signal (another node's worker died /
            # elastic manager bumped the generation): kill + re-rendezvous.
            # A node whose own workers already finished STAYS in this loop
            # until every node is done, so it rejoins a restart generation
            # instead of deadlocking peers (pod-restart semantics: the
            # whole job re-runs, as in the reference --max_restart policy).
            if rdv is not None:
                gen = rdv.restart_gen()
                if gen > current_gen:
                    if restarts_used >= args.max_restart:
                        sys.exit(1)
                    restarts_used += 1
                    _kill_pod(procs)
                    current_gen, procs = _spawn_gen(gen)
                    local_done = done_marked = False
                    continue

            if local_done:
                if rdv.finish_done_count(current_gen) >= rdv.nnodes:
                    rdv.leave(current_gen)
                    break
                if time.time() > done_deadline:
                    # a peer died without marking done: our work succeeded,
                    # don't hang forever (bounded by --rdv_timeout)
                    break
                continue

            statuses = [p.poll() for p in procs]
            failed = [r for r in statuses if r not in (None, 0)]
            if failed:
                if restarts_used < args.max_restart:
                    restarts_used += 1
                    _kill_pod(procs)
                    if rdv is not None:
                        # take the max of our bump and the live counter so
                        # a concurrent peer failure doesn't look like a
                        # *new* generation next poll (one fault, one
                        # restart)
                        current_gen = max(rdv.bump_restart(),
                                          rdv.restart_gen())
                        current_gen, procs = _spawn_gen(current_gen)
                    else:
                        procs = _build_and_spawn(current_gen)
                    continue
                exit_code = failed[0]
                if rdv is not None:
                    # signal peers: their pods must not wait forever on a
                    # dead member — the bump makes them restart and, once
                    # their own budget is exhausted, exit too
                    try:
                        rdv.bump_restart()
                    except Exception:
                        pass
                break
            if all(r == 0 for r in statuses):
                if rdv is None:
                    break
                if not done_marked:
                    rdv.mark_done(current_gen)
                    done_marked = True
                local_done = True
                done_deadline = time.time() + rdv.timeout
    except SystemExit:
        raise
    except Exception:
        # a dead store / broken rendezvous must not orphan the pod
        exit_code = exit_code or 1
        raise
    finally:
        _kill_pod(procs)
    sys.exit(exit_code)


def main():
    launch()


if __name__ == "__main__":
    main()
