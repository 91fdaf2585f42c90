"""Replica schedule engines in host worker processes.

`ScheduleEngine.evaluate_population` hands a batch of genomes to a
`SchedulePool` when the batch walks enough CNs to pay for the pipes
(`POOL_MIN_WALKS`). Each worker process holds a replica engine, built once
from the parent engine's graph, cost model and accelerator, with a
checkpoint store of its own, and scores a contiguous, even share of the
batch's rows with the engine's one `schedule`. A row's (latency, energy)
is a pure function of its allocation (a resumed schedule is bit-identical
to a cold one), so the batch, put back in input order, is the serial
loop's bit for bit; the split decides only how many CNs are resumed.

Process safety: a worker is a fresh interpreter, spawned and never
forked (the parent may hold a TPU runtime and JAX's threads), started from
this module's `_serve` and not from the caller's `__main__`, which it never
imports, so a script without a `__main__` guard is safe. It imports only
the NumPy side of `repro.core`: `repro.core.scheduler` imports no JAX, and
the graph is shipped without its engine cache, whose engines may hold JAX
programs. A worker exits when the parent closes its pipe or dies (end of
file). A process keeps at most one pool: starting one closes the one
before, and a pool is also closed when its engine is collected or the
interpreter exits (`weakref.finalize`). A process that is itself a pool
worker, this pool's or a sweep executor's, starts no pool of its own.
"""
from __future__ import annotations

import copy
import multiprocessing
import os
import pickle
import subprocess
import sys
import weakref
from multiprocessing.connection import Connection

import numpy as np

# A batch walking fewer CNs (rows x CNs a schedule) than this stays serial:
# replaying recorded GA batches on an 8-CPU host with 4 and 7 workers,
# pooled ones were at times slower below it and never above; on the
# 13-CPU host of a one-chip TPU v5e machine, with 12 workers, never above
# 5409 walks (PERF.md section 6).
POOL_MIN_WALKS = 8_000
# Most workers a pool starts, whatever the spare CPUs: a worker holds its
# own replica (73 MB for a 4816-CN graph), and each one past the first
# four saves less (PERF.md section 6: 4 and 12 workers were replayed on
# the 13-CPU host, 8 was not).
POOL_MAX_WORKERS = 8

# the replica engine, in a worker process (None elsewhere)
_REPLICA = None
# the process's one live pool
_LIVE: "SchedulePool | None" = None


class WorkerLost(RuntimeError):
    """A worker process exited; the pool has closed itself."""


def cpu_limit(cgroup: str = "/sys/fs/cgroup") -> int:
    """CPUs this process may keep busy: its affinity mask, bounded by the
    CPU quota of its cgroup (v2 `cpu.max`, else v1 `cpu.cfs_quota_us`
    over `cpu.cfs_period_us`) where one is set."""
    cpus = (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
            else os.cpu_count() or 1)
    for files in (("cpu.max",),
                  ("cpu/cpu.cfs_quota_us", "cpu/cpu.cfs_period_us")):
        try:
            words = []
            for name in files:
                with open(os.path.join(cgroup, name)) as f:
                    words += f.read().split()
        except OSError:
            continue
        if len(words) != 2 or words[0] in ("max", "-1"):
            return cpus
        return max(1, min(cpus, int(words[0]) // int(words[1])))
    return cpus


def pool_workers(cap: int) -> int:
    """Workers a pool may start in this process: none in a process that is
    itself a pool worker, none where at most one CPU is spare, else one
    per spare CPU up to `cap`."""
    if _REPLICA is not None or multiprocessing.parent_process() is not None:
        return 0
    spare = cpu_limit() - 1
    return min(spare, cap) if spare > 1 else 0


class SchedulePool:
    """`workers` replicas of `engine` in spawned processes (see the module
    docstring). The pool holds no reference to the engine; starting it
    closes the process's pool before it."""

    def __init__(self, engine, workers: int):
        global _LIVE
        if _LIVE is not None:
            _LIVE.close()
        graph = copy.copy(engine.graph)
        graph.__dict__.pop("_engine_cache", None)
        blob = pickle.dumps((graph, engine.cost_model, engine.accelerator),
                            protocol=pickle.HIGHEST_PROTOCOL)
        self._conns: list = []
        self._procs: list = []
        self.closed = False
        self._finalizer = weakref.finalize(engine, self.close)
        # the parent's import path, which a script may have extended
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
        try:
            for _ in range(workers):
                conn, child = multiprocessing.Pipe()
                fd = child.fileno()
                self._procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     f"from repro.core.schedpool import _serve; _serve({fd})"],
                    pass_fds=(fd,), env=env, stdin=subprocess.DEVNULL))
                child.close()
                self._conns.append(conn)
            # every worker is starting; each builds its replica as soon as
            # it has read the inputs, so the builds overlap
            for conn in self._conns:
                conn.send_bytes(blob)
            for i in range(workers):
                self._recv(i)
        except BaseException:
            self.close()
            raise
        _LIVE = self

    @property
    def workers(self) -> int:
        return len(self._conns)

    def evaluate(self, genomes: np.ndarray, kw: dict
                 ) -> tuple[np.ndarray, list[int], dict[str, int]]:
        """(P, 2) objectives of a (P, G) batch in input order, the CNs each
        row resumed, and the workers' checkpoint-counter increments."""
        parts = [p for p in np.array_split(genomes, self.workers) if len(p)]
        replies = self._run([(_score_rows, (p, kw)) for p in parts])
        ckpt: dict[str, int] = {}
        for _, _, stats in replies:
            for k, v in stats.items():
                ckpt[k] = ckpt.get(k, 0) + v
        return (np.concatenate([vals for vals, _, _ in replies]),
                [k for _, res, _ in replies for k in res], ckpt)

    def _run(self, tasks: list) -> list:
        """Send the first workers one `(fn, args)` task each, then collect
        `fn(*args)` from each. A task's exception is raised here once
        every reply is in; a lost worker or an interrupt closes the pool,
        since replies may then be left in the pipes."""
        try:
            for i, task in enumerate(tasks):
                try:
                    self._conns[i].send(task)
                except OSError as e:
                    raise self._lost(i) from e
            replies, error = [], None
            for i in range(len(tasks)):
                ok, value = self._recv(i)
                if not ok and error is None:
                    error = value
                replies.append(value)
        except BaseException:
            self.close()
            raise
        if error is not None:
            raise error
        return replies

    def _recv(self, i: int):
        try:
            return self._conns[i].recv()
        except (EOFError, OSError) as e:
            raise self._lost(i) from e

    def _lost(self, i: int) -> WorkerLost:
        return WorkerLost(f"schedule pool worker {i} exited "
                          f"(exit code {self._procs[i].poll()})")

    def close(self) -> None:
        """Stop the workers and wait for them (idempotent)."""
        global _LIVE
        if self.closed:
            return
        self.closed = True
        if _LIVE is self:
            _LIVE = None
        self._finalizer.detach()
        for conn in self._conns:
            try:
                conn.send(None)
            except (OSError, ValueError):
                pass                     # the worker is gone already
            conn.close()
        for proc in self._procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


def _serve(fd: int) -> None:
    """A worker: build the replica engine from the parent's inputs, then
    run `(fn, args)` tasks until the parent sends None or goes away."""
    global _REPLICA
    from repro.core.scheduler import ScheduleEngine
    conn = Connection(fd)
    try:
        graph, cost_model, accelerator = pickle.loads(conn.recv_bytes())
        _REPLICA = ScheduleEngine(graph, cost_model, accelerator)
        conn.send(True)
        while (task := conn.recv()) is not None:
            fn, args = task
            try:
                reply = (True, fn(*args))
            except Exception as e:       # the parent raises it
                reply = (False, e)
            conn.send(reply)
    except (EOFError, OSError):          # the parent is gone
        pass


def _score_rows(genomes: np.ndarray, kw: dict
                ) -> tuple[np.ndarray, list[int], dict[str, int]]:
    """A worker's share of a batch: objectives, the CNs each row resumed
    (a resume adds exactly its CNs to `cns_skipped`), and the increments
    of the replica's checkpoint counters."""
    engine = _REPLICA
    stats = engine.ckpt_stats
    before = dict(stats)
    out = np.empty((len(genomes), 2), dtype=np.float64)
    resumed = []
    for r in range(len(genomes)):
        skipped = stats["cns_skipped"]
        res = engine.schedule(genomes[r], record=False, **kw)
        out[r, 0] = res.latency_cc
        out[r, 1] = res.energy_pj
        resumed.append(stats["cns_skipped"] - skipped)
    return out, resumed, {k: stats[k] - before[k] for k in stats}
