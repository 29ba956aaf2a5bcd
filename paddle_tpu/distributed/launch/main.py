"""Launcher CLI (reference: python/paddle/distributed/launch/main.py ==
``fleetrun``: spawn per-device workers, set PADDLE_* env, watch loop,
restart on failure).

TPU-native: ONE process per host drives all local chips (SPMD), so
``--nnodes`` is the only real fan-out; per-host we spawn a single worker
(vs the reference's one-per-GPU).  A chip belongs to one process, so
this parent never initialises a JAX backend (``import paddle_tpu`` does
not either) and on a TPU host ``--nproc_per_node > 1`` is refused.  The
watch loop + restart-with-resume
survives worker crashes; rendezvous is the JAX coordinator (the reference's
TCPStore master).  With ``--nnodes min:max`` the launcher also runs the
elastic membership watch: the registry store listens on master_port+1 (the
master port itself belongs to the workers' rendezvous), and on membership
change workers are relaunched with rank/world recomputed from the live
member set.
"""
import argparse
import os
import signal
import subprocess
import sys
import time

from ...device import chip as _chip
from ...framework import failpoints as _fp
from ...framework.backoff import jittered_delay
from ...framework.preemption import PREEMPTED_EXIT_CODE
from ..fleet import elastic as _elastic_mod
from ..fleet.elastic import ElasticManager, ElasticStatus

# restart hygiene: sleep with exponential backoff between restarts of the
# same worker (a crash-looping script must not spin the host), and forgive
# the restart budget once a worker has run stably for this long — a job
# that hiccups once a day should never exhaust max_restart
_RESTART_BACKOFF_BASE = 1.0
_RESTART_BACKOFF_CAP = 60.0
_STABLE_WINDOW_S = float(os.environ.get("PADDLE_STABLE_WINDOW", "60"))


def _restart_backoff(n_restarts):
    """Jittered exponential backoff (seconds) before restart N."""
    return jittered_delay(max(n_restarts - 1, 0),
                          _RESTART_BACKOFF_BASE, _RESTART_BACKOFF_CAP)


class _RestartPolicy:
    """Per-worker restart accounting shared by the collective and PS
    watch loops: backoff deadlines (never blocking the loop),
    stable-window budget forgiveness, and a preemption budget separate
    from (and more generous than) the crash budget."""

    def __init__(self, max_restart):
        self.max_restart = max_restart
        self.restarts = {}
        self.preempts = {}
        self.started_at = {}
        self.pending = {}       # key -> earliest restart time

    def note_start(self, key):
        self.started_at[key] = time.time()

    def is_pending(self, key):
        return key in self.pending

    def has_pending(self):
        return bool(self.pending)

    def pop_due(self, now):
        """Keys whose backoff has elapsed; removed from pending."""
        due = [k for k, t in self.pending.items() if now >= t]
        for k in due:
            del self.pending[k]
        return due

    def reset_all(self):
        self.pending.clear()
        self.restarts.clear()
        self.preempts.clear()

    def on_exit(self, key, ret, now, label):
        """Handle a non-zero exit: schedule a restart (returns
        ``"restart"``, key parked in ``pending``) or ``"give_up"``."""
        # stable-window forgiveness, with the bar rising per CRASH on
        # record: a fixed window would let a worker that deterministically
        # crashes just past it restart forever, never exhausting
        # max_restart — scaling by crash count guarantees any fixed
        # crash interval eventually stops qualifying.  Preemptions do
        # NOT raise the bar: a pool legitimately evicting workers every
        # few minutes must keep qualifying for forgiveness, or a healthy
        # checkpoint-and-resume job would exhaust the preempt budget.
        crash_history = self.restarts.get(key, 0)
        window = _STABLE_WINDOW_S * (1 + crash_history)
        if (crash_history or self.preempts.get(key)) and \
                now - self.started_at.get(key, 0) >= window:
            print(f"[launch] {label} was stable for >{window:.0f}s; "
                  "resetting its restart budget", flush=True)
            self.restarts[key] = 0
            self.preempts[key] = 0
        if ret == PREEMPTED_EXIT_CODE:
            # the worker saved an emergency checkpoint and asked to be
            # relaunched (framework/preemption.py contract): restart
            # with resume, without charging the crash budget — but a
            # worker that does nothing except exit 71 is a bug, so a
            # generous separate budget still bounds the loop
            self.preempts[key] = self.preempts.get(key, 0) + 1
            if self.preempts[key] > max(3 * self.max_restart, 10):
                print(f"[launch] {label} preempted {self.preempts[key]} "
                      "times without a stable run; giving up", flush=True)
                return "give_up"
            backoff = _restart_backoff(min(self.preempts[key], 3))
            print(f"[launch] {label} preempted (rc={ret}); restarting "
                  f"with resume from its latest checkpoint in "
                  f"{backoff:.1f}s", flush=True)
        else:
            if self.restarts.get(key, 0) >= self.max_restart:
                print(f"[launch] {label} failed rc={ret}; giving up",
                      flush=True)
                return "give_up"
            self.restarts[key] = self.restarts.get(key, 0) + 1
            backoff = _restart_backoff(self.restarts[key])
            print(f"[launch] {label} exited rc={ret}; restart "
                  f"{self.restarts[key]}/{self.max_restart} in "
                  f"{backoff:.1f}s", flush=True)
        self.pending[key] = now + backoff
        return "restart"


def _parse():
    p = argparse.ArgumentParser(prog="paddle_tpu.distributed.launch")
    p.add_argument("--nnodes", type=str, default="1",
                   help="node count (N or min:max for elastic)")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER", ""))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="workers per host (1 on TPU: SPMD drives all chips)")
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--run_mode", type=str, default="collective",
                   help="collective | ps")
    p.add_argument("--server_num", type=int, default=0,
                   help="PS mode: number of parameter servers to spawn")
    p.add_argument("--trainer_num", type=int, default=None,
                   help="PS mode: number of trainer workers to spawn")
    p.add_argument("--servers", type=str, default="",
                   help="PS mode: comma list of host:port server endpoints"
                        " (default 127.0.0.1 with sequential ports)")
    p.add_argument("--devices", "--gpus", type=str, default=None,
                   help="accepted for compat; chip selection is automatic")
    p.add_argument("--ckpt_root", type=str,
                   default=os.environ.get("PADDLE_CKPT_ROOT", ""),
                   help="manifest-checkpoint root for elastic resume: "
                        "exported to every worker as PADDLE_CKPT_ROOT "
                        "AND PADDLE_RESUME_ROOT, so the trainer script "
                        "resumes from the newest committed manifest "
                        "step via Model.fit(resume=) — an empty root "
                        "is a fresh start, making resume a property of "
                        "the on-disk state rather than launcher-local "
                        "restart history")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


def _worker_env(args, local_rank, membership):
    """membership: {"node_index": i, "n_nodes": n, "endpoints": [...]}
    — static from --node_rank/--nnodes, or live from the elastic store.
    With a ``--ckpt_root`` configured, EVERY start points the worker at
    the manifest root via ``PADDLE_RESUME_ROOT``: the trainer passes it
    to ``Model.fit(resume=...)``, which treats an empty root as a fresh
    start — so whether this launch resumes is decided by the on-disk
    checkpoint state, not launcher-local restart history (a freshly
    rebooted launcher rejoining an elastic job must restore the same
    checkpoint its surviving peers do, or ranks diverge)."""
    env = dict(os.environ)
    nproc = args.nproc_per_node
    world = membership["n_nodes"] * nproc
    rank = membership["node_index"] * nproc + local_rank
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TRAINERS_NUM"] = str(world)
    env["PADDLE_LOCAL_RANK"] = str(local_rank)
    if args.master:
        env["PADDLE_MASTER"] = args.master
    if membership.get("endpoints"):
        # one endpoint per TRAINER: expand each node's base port by
        # local_rank so len(endpoints) == world size
        expanded = []
        for ep in membership["endpoints"]:
            if ":" in ep:
                h, prt = ep.rsplit(":", 1)
                # ':0' is ElasticManager.start()'s "no port" placeholder,
                # not a real base — fall back like the empty case
                base = int(prt) if prt and int(prt) != 0 else 6170
            else:
                h, base = ep, 6170
            for lr in range(nproc):
                expanded.append(f"{h}:{base + lr}")
        env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(expanded)
    env["PADDLE_CURRENT_ENDPOINT"] = \
        f"{os.environ.get('POD_IP', '127.0.0.1')}:{6170 + local_rank}"
    if getattr(args, "ckpt_root", ""):
        env["PADDLE_CKPT_ROOT"] = args.ckpt_root
        env["PADDLE_RESUME_ROOT"] = args.ckpt_root
    # restarts and relaunches reuse compiled programs: JAX reads this
    # variable itself (a value already set is kept as it is)
    env["JAX_COMPILATION_CACHE_DIR"] = _chip.compile_cache_dir()
    return env


def _note_reshard(old_np, new_np, root):
    """Book a restart-with-resume at a changed world size: fire the
    ``elastic.reshard`` failpoint, count ``pt_checkpoint_reshard_total``
    and emit the ``elastic_reshard`` guardian event — the observable
    record that the job is resuming on different capacity."""
    if _fp._ACTIVE:
        _fp.fire(_elastic_mod.FP_RESHARD)
    try:
        from ... import observability as _obs
        if _obs.enabled():
            _obs.inc("pt_checkpoint_reshard_total", kind="relaunch")
    except Exception:
        pass
    try:
        from ...framework import guardian as _guardian
        _guardian.emit("elastic_reshard", old_np=int(old_np),
                       new_np=int(new_np), root=str(root or ""),
                       source="relaunch")
    except Exception:
        print(f"[launch] elastic reshard: np {old_np} -> {new_np} "
              f"(resume root {root!r})", flush=True)


def _elastic_registry_endpoint(master):
    """Elastic store rides master_port+1: the master port itself is the
    workers' rendezvous (jax coordinator / MasterStore) and must stay
    free for them."""
    host, _, port = master.partition(":")
    return host or "127.0.0.1", int(port or 6768) + 1


def _setup_elastic(args):
    """min:max nnodes + a master endpoint → store-backed ElasticManager
    (node 0 hosts the registry store, mirroring the reference's ETCD)."""
    if ":" not in str(args.nnodes) or not args.master:
        return None
    from ..store import TCPStore
    host, port = _elastic_registry_endpoint(args.master)
    store = None
    if args.node_rank == 0:
        store = TCPStore(host, port, is_master=True)
    mgr = ElasticManager(np=args.nnodes, store=store,
                         master=f"{host}:{port}" if store is None else None)
    mgr.start(endpoint=f"{os.environ.get('POD_IP', '127.0.0.1')}:6170")
    print(f"[launch] elastic: np={args.nnodes} registered as node "
          f"{mgr._node_id}", flush=True)
    # gate the first launch on quorum: starting below min_np would train
    # with the wrong world size
    got = mgr.wait_for_np()
    if not got:
        print(f"[launch] elastic: quorum of {mgr.min_np} nodes not reached "
              f"within {mgr.elastic_timeout}s (observed {int(got)} "
              f"member(s)); aborting", flush=True)
        mgr.stop()
        sys.exit(1)
    return mgr


def _elastic_membership(elastic, args):
    """Live rank/world from the member set (node order = node-id order).
    node_index is None when this node was capped out by max_np — it must
    stand by, not train with a colliding rank."""
    members = elastic._members()
    ids = sorted(members)
    try:
        idx = ids.index(elastic._node_id)
    except ValueError:
        idx = None
    return {"node_index": idx, "n_nodes": max(len(ids), 1),
            "endpoints": [members[i] for i in ids]}


def _launch_ps(args):
    """PS-mode controller (reference: launch/controllers/ps.py): spawn
    ``server_num`` PSERVER processes + ``trainer_num`` TRAINER processes
    with the PADDLE_* role env, watch, restart trainers on failure
    (servers are stateful — a dead server fails the job)."""
    import socket

    os.makedirs(args.log_dir, exist_ok=True)
    n_srv = args.server_num or 1
    n_trn = args.trainer_num if args.trainer_num is not None else 1
    if args.servers:
        endpoints = [e for e in args.servers.split(",") if e]
    else:
        # hold every probe socket until all ports are drawn, or the
        # kernel can hand the same ephemeral port out twice
        probes = []
        endpoints = []
        for _ in range(n_srv):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            probes.append(s)
            endpoints.append(f"127.0.0.1:{s.getsockname()[1]}")
        for s in probes:
            s.close()
    ep_list = ",".join(endpoints)
    procs, logs = {}, {}
    policy = _RestartPolicy(args.max_restart)

    def start(kind, idx):
        key = (kind, idx)
        log_path = os.path.join(args.log_dir, f"{kind}log.{idx}")
        if key in logs:
            logs[key].close()        # restart: don't leak the old handle
        logf = open(log_path, "ab", buffering=0)
        logs[key] = logf
        env = dict(os.environ)
        # scrub any collective-mode env leaked from the parent (a PS
        # worker inheriting PADDLE_MASTER/TRAINER_ENDPOINTS would try a
        # collective rendezvous nobody is serving)
        for stale in ("PADDLE_MASTER", "PADDLE_TRAINER_ENDPOINTS",
                      "PADDLE_CURRENT_ENDPOINT", "PADDLE_NODE_RANK",
                      "PADDLE_LOCAL_RANK", "PADDLE_TRAINER_ID",
                      "TRAINING_ROLE", "POD_IP", "PADDLE_PORT"):
            env.pop(stale, None)
        env["PADDLE_PSERVERS_IP_PORT_LIST"] = ep_list
        env["PADDLE_TRAINERS_NUM"] = str(n_trn)
        if kind == "server":
            host, _, port = endpoints[idx].rpartition(":")
            env["TRAINING_ROLE"] = "PSERVER"
            env["POD_IP"] = host or "127.0.0.1"
            env["PADDLE_PORT"] = port
        else:
            env["TRAINING_ROLE"] = "TRAINER"
            env["PADDLE_TRAINER_ID"] = str(idx)
        cmd = [sys.executable, args.script] + args.script_args
        p = subprocess.Popen(cmd, env=env, stdout=logf,
                             stderr=subprocess.STDOUT)
        procs[key] = p
        policy.note_start(key)
        print(f"[launch] started {kind} {idx} pid={p.pid} log={log_path}",
              flush=True)

    def stop_all(code):
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        t0 = time.time()
        while any(p.poll() is None for p in procs.values()) and \
                time.time() - t0 < 10:
            time.sleep(0.2)
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        sys.exit(code)

    for i in range(n_srv):
        start("server", i)
    for i in range(n_trn):
        start("trainer", i)

    while True:
        trainers_alive = 0
        now = time.time()
        for kind, idx in policy.pop_due(now):   # backoff elapsed
            start(kind, idx)
        for (kind, idx), p in list(procs.items()):
            key = (kind, idx)
            if policy.is_pending(key):
                trainers_alive += 1      # restart-pending counts as live
                continue
            ret = p.poll()
            if ret is None:
                if kind == "trainer":
                    trainers_alive += 1
                continue
            if kind == "server":
                # ANY server exit while trainers still run is fatal —
                # rc==0 (script forgot run_server) strands trainers on a
                # dead endpoint with a misleading eventual diagnosis
                print(f"[launch] server {idx} exited rc={ret} before the "
                      "trainers finished; aborting", flush=True)
                stop_all(1)
            if kind == "trainer" and ret != 0:
                if policy.on_exit(key, ret, now,
                                  f"trainer {idx}") == "give_up":
                    stop_all(1)
                trainers_alive += 1
        if trainers_alive == 0 and \
                all(p.poll() is not None or k[0] == "server"
                    for k, p in procs.items()):
            # every trainer finished cleanly: job done, retire servers
            print("[launch] all trainers finished; stopping servers",
                  flush=True)
            for (kind, _), p in procs.items():
                if kind == "server" and p.poll() is None:
                    p.terminate()
            for p in procs.values():
                if p.poll() is None:
                    p.wait()
            return
        time.sleep(0.5)


def main():
    args = _parse()
    if args.run_mode == "ps" or args.server_num > 0:
        _launch_ps(args)
        return
    if args.nproc_per_node > 1 and _chip.child_would_claim_tpu():
        sys.exit(
            f"[launch] --nproc_per_node={args.nproc_per_node} on a TPU "
            "host: a chip belongs to one process and every worker would "
            "claim all local chips.  One process drives all local chips "
            "(SPMD) — use --nproc_per_node 1 (assigning chips per "
            "worker is not implemented)")
    os.makedirs(args.log_dir, exist_ok=True)
    procs = {}
    policy = _RestartPolicy(args.max_restart)
    logs = {}
    elastic = _setup_elastic(args)
    membership = {"node_index": args.node_rank,
                  "n_nodes": int(str(args.nnodes).split(":")[0]),
                  "endpoints": []}
    if elastic is not None:
        membership = _elastic_membership(elastic, args)
        if membership["node_index"] is None:
            print("[launch] elastic: this node is beyond max_np; exiting",
                  flush=True)
            elastic.stop()
            sys.exit(1)

    def start(local_rank):
        log_path = os.path.join(args.log_dir, f"workerlog.{local_rank}")
        if local_rank in logs:
            logs[local_rank].close()  # restart: don't leak the old handle
        logf = open(log_path, "ab", buffering=0)
        logs[local_rank] = logf
        cmd = [sys.executable, args.script] + args.script_args
        p = subprocess.Popen(cmd, env=_worker_env(args, local_rank,
                                                  membership),
                             stdout=logf, stderr=subprocess.STDOUT)
        procs[local_rank] = p
        policy.note_start(local_rank)
        print(f"[launch] started worker {local_rank} pid={p.pid} "
              f"rank={membership['node_index'] * args.nproc_per_node + local_rank} "
              f"world={membership['n_nodes'] * args.nproc_per_node} "
              f"log={log_path}", flush=True)

    def stop_workers():
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        t0 = time.time()
        while any(p.poll() is None for p in procs.values()) and \
                time.time() - t0 < 10:
            time.sleep(0.2)
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()                 # reap — no zombies

    def shutdown(signum=None, frame=None, code=None):
        if elastic is not None:
            elastic.stop()               # mark this node dead immediately
        stop_workers()
        sys.exit(code if code is not None else (1 if signum else 0))

    signal.signal(signal.SIGINT, shutdown)
    signal.signal(signal.SIGTERM, shutdown)

    for i in range(args.nproc_per_node):
        start(i)

    # watch loop (reference: controllers/controller.py::watch +
    # elastic/manager.py membership watch)
    holding = False
    hold_since = None
    # the world size workers are ACTUALLY running at — `membership` is
    # recomputed on every hold/restart pass (including capped-out holds
    # that never relaunch), so the reshard event's old_np must come
    # from the last world that really ran, not the latest snapshot
    active_world = membership["n_nodes"] * args.nproc_per_node
    while True:
        status = elastic.watch() if elastic is not None else None
        if status == ElasticStatus.HOLD:
            # below min nodes: pause failure accounting — crashed workers
            # stay down (their restart budget untouched) until membership
            # recovers (RESTART) or the elastic timeout expires
            if not holding:
                print("[launch] elastic: below min nodes, holding",
                      flush=True)
                holding = True
                hold_since = time.time()
            if time.time() - hold_since > elastic.elastic_timeout * 4:
                print("[launch] elastic: membership never recovered; "
                      "giving up", flush=True)
                shutdown(code=1)
            # still reap finished workers so a completed job can exit —
            # but a worker parked awaiting its restart-backoff deadline
            # is dead-by-design, not "done"
            if not policy.has_pending() and \
                    all(p.poll() is not None for p in procs.values()):
                rcs = [p.returncode for p in procs.values()]
                code = 0 if all(r == 0 for r in rcs) else 1
                print(f"[launch] workers done during hold rcs={rcs}",
                      flush=True)
                shutdown(code=code)
            time.sleep(1)
            continue
        if status == ElasticStatus.RESTART or \
                (holding and status == ElasticStatus.NORMAL):
            holding = False
            # re-read the OBSERVED member count: the relaunch runs at
            # whatever np the cluster actually gives back right now,
            # not the snapshot the watch() poll happened to see
            observed = elastic.wait_for_np()
            if not observed:
                print(f"[launch] elastic: membership changed but only "
                      f"{int(observed)} member(s) observed; holding",
                      flush=True)
                holding = True
                hold_since = time.time()
                time.sleep(1)
                continue
            old_world = active_world
            membership = _elastic_membership(elastic, args)
            if membership["node_index"] is None:
                # capped out by max_np: stand by until a slot opens
                print("[launch] elastic: beyond max_np, standing by",
                      flush=True)
                stop_workers()
                holding = True
                hold_since = time.time()
                time.sleep(1)
                continue
            new_world = membership["n_nodes"] * args.nproc_per_node
            print(f"[launch] elastic membership changed → relaunch as "
                  f"node {membership['node_index']} of "
                  f"{membership['n_nodes']} (observed np="
                  f"{int(observed)}): {membership['endpoints']}",
                  flush=True)
            stop_workers()
            policy.reset_all()           # fresh budget for the new epoch
            if args.ckpt_root and old_world != new_world:
                # the relaunch resumes at a DIFFERENT world size: the
                # workers will reshard the newest committed manifest
                # step onto the new mesh.  Same-size membership churn
                # (node replaced, quorum dip-and-recover) still resumes
                # but is not a reshard — booking it would make the
                # event/counter useless for alerting.
                _note_reshard(old_world, new_world, args.ckpt_root)
            active_world = new_world
            for i in range(args.nproc_per_node):
                start(i)

        alive = 0
        now = time.time()
        for i in policy.pop_due(now):    # backoff elapsed: relaunch
            start(i)
        for i, p in list(procs.items()):
            if policy.is_pending(i):
                alive += 1               # restart-pending counts as live
                continue
            ret = p.poll()
            if ret is None:
                alive += 1
            elif ret != 0:
                if policy.on_exit(i, ret, now,
                                  f"worker {i}") == "give_up":
                    shutdown(code=1)
                alive += 1
        if alive == 0:
            break
        time.sleep(1)
    if elastic is not None:
        elastic.stop()
    print("[launch] all workers finished", flush=True)


if __name__ == "__main__":
    main()
