"""paddle.distributed.spawn (reference: python/paddle/distributed/spawn.py
— fork N workers with per-rank PADDLE_* env for single-node tests and
notebooks).

TPU-native notes: on TPU one process drives all local chips (SPMD), so
``nprocs>1`` is the CPU-collective test path (the reference's Gloo story):
children are started with the ``spawn`` start method and rank env set
before import, and rendezvous through PADDLE_MASTER.  nprocs==1 runs
inline — sharding, not processes, is the parallelism on-device.
"""
import os
import socket

__all__ = ["spawn", "MultiprocessContext"]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_entry(rank, nprocs, master, base_port, env_extra, func, args):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_LOCAL_RANK"] = str(rank)
    os.environ["PADDLE_MASTER"] = master
    os.environ["PADDLE_CURRENT_ENDPOINT"] = f"127.0.0.1:{base_port + rank}"
    for k, v in (env_extra or {}).items():
        os.environ[k] = str(v)
    func(*args)


class MultiprocessContext:
    def __init__(self, processes):
        self.processes = processes

    def join(self, timeout=None):
        for p in self.processes:
            p.join(timeout)
        for rank, p in enumerate(self.processes):
            if p.is_alive():
                raise TimeoutError(
                    f"spawned worker {rank} still running after join("
                    f"timeout={timeout}) — terminate() it or wait longer")
            if p.exitcode != 0:
                raise RuntimeError(
                    f"spawned worker {rank} exited with code {p.exitcode}")
        return True

    def terminate(self):
        for p in self.processes:
            if p.is_alive():
                p.terminate()


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Run ``func`` on ``nprocs`` workers (rank env pre-set).  nprocs<=1
    runs inline and returns None; otherwise returns a
    MultiprocessContext (joined first when ``join=True``)."""
    if nprocs in (-1, 0, 1):
        os.environ.setdefault("PADDLE_TRAINER_ID", "0")
        os.environ.setdefault("PADDLE_TRAINERS_NUM", "1")
        os.environ.setdefault("PADDLE_MASTER", "127.0.0.1:6768")
        func(*args)
        return None
    import multiprocessing as mp
    ctx = mp.get_context("spawn")
    master = f"127.0.0.1:{_free_port()}"
    # per-run trainer base port (like the master port): fixed 6170+rank
    # endpoints collide when two spawn() runs share the machine (e.g.
    # parallel test workers)
    base_port = _free_port()
    env_extra = dict(options.get("env", {}))
    # a chip belongs to one process: several workers cannot share the
    # host's TPU, and they are never quietly moved to the CPU instead.
    # Workers inherit JAX_PLATFORMS; CPU workers on a TPU host are asked
    # for with options={"env": {"JAX_PLATFORMS": "cpu"}}
    from ..device import chip as _chip
    if _chip.child_would_claim_tpu({**os.environ, **env_extra}):
        raise RuntimeError(
            f"distributed.spawn(nprocs={nprocs}) on a TPU host: every "
            "worker would claim the local chips.  One process drives "
            "all local chips (SPMD); pass options={'env': "
            "{'JAX_PLATFORMS': 'cpu'}} for CPU workers")
    procs = []
    for rank in range(nprocs):
        # set env in the PARENT around start(): spawn children inherit it
        # at exec, so even module-import-time code in the child sees its
        # rank/platform (then _worker_entry re-asserts it)
        saved = {}
        child_env = {
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_LOCAL_RANK": str(rank),
            "PADDLE_MASTER": master,
            "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{base_port + rank}",
            **{k: str(v) for k, v in env_extra.items()},
        }
        for k, v in child_env.items():
            saved[k] = os.environ.get(k)
            os.environ[k] = v
        try:
            p = ctx.Process(
                target=_worker_entry,
                args=(rank, nprocs, master, base_port, env_extra, func,
                      tuple(args)),
                daemon=daemon)
            p.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        procs.append(p)
    context = MultiprocessContext(procs)
    if join:
        context.join()
    return context
