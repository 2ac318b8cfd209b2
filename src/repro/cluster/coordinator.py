"""Multi-worker, fault-tolerant driver for the two-pass streaming solve.

The :class:`ClusterEngine` owns a pool of workers (threads standing in
for hosts — the state logistics, not the transport, are what this module
implements; see ``repro.train.elastic`` for the same stance on training)
and fans the streaming engine's two passes out across them:

- **pass 1** (``cluster_sketch``): each worker streams its tile-aligned
  row range into its own mergeable
  :class:`~repro.streaming.accumulate.SketchAccumulator`, checkpointing
  the partial state every ``checkpoint_every`` tiles
  (``repro.cluster.checkpoint``).  The coordinator merges the per-range
  partials associatively (``merge_all`` — the same reduction
  ``sharded_sketch`` runs as a psum) in deterministic range order.
- **pass 2** (``matvec`` / ``rmatvec`` / ``residual_grad``): the blocked
  products of the iteration are computed per-range and placed/summed in
  range order — stateless, so a failed range is simply recomputed.

Fault tolerance is first-class, not a retry loop:

- every worker heartbeats per tile; the coordinator's monitor declares a
  worker dead when its beat goes stale (``heartbeat_timeout``) or its
  thread dies (:class:`~repro.cluster.faults.WorkerKilled`),
- a dead worker's unfinished ranges are REASSIGNED to the live worker
  with the least remaining work (``OwnershipMap.reassign`` — the
  ``rebalance_microbatch`` arithmetic on tiles), respawning a fresh
  worker only when nobody is left,
- a reassigned sketch range resumes from its last accumulator
  checkpoint: only the tiles since the watermark are re-streamed, and
  the resumed partial is bit-equal to an uninterrupted one,
- late results from workers that were *declared* dead but are still
  running (network-partition zombies), and deliberate double
  submissions, are dropped by per-range dedup before the merge
  (``duplicates_dropped`` in ``stats``).

The engine quacks like a :class:`~repro.streaming.sources.RowSource`
(shape/dtype/tiles), and the streaming drivers probe for its
``cluster_sketch`` / ``matvec`` / ``rmatvec`` / ``residual_grad``
methods — so ``stream_lstsq(..., cluster=ClusterSpec(...))``,
``StreamingSolver(..., cluster=...)`` and ``lstsq(source, b, key,
cluster=...)`` all run their streams through the pool unchanged.
"""
from __future__ import annotations

import dataclasses
import os
import queue
import shutil
import tempfile
import threading
import time

import jax.numpy as jnp

from ..kernels.common import matmul
from ..obs import trace as obs_trace
from ..obs.lockcheck import make_lock
from ..obs.metrics import REGISTRY
from ..streaming.accumulate import make_accumulator, merge_all
from ..streaming.sources import RowSource, as_source
from . import checkpoint as cckpt
from .faults import WorkerKilled, as_plan
from .shard import OwnershipMap, RowRange, RowRangeSource, partition_rows

__all__ = ["ClusterSpec", "ClusterEngine", "ClusterFailure"]


class ClusterFailure(RuntimeError):
    """The pass cannot complete: recovery budget exhausted."""


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """Configuration of a cluster run (pass through ``lstsq(cluster=...)``).

    ``num_workers``        worker pool size (≥ 1; 1 degenerates to the
                           single-stream engine plus checkpoints).
    ``tile_rows``          global tile grid (None → the source's tiling).
    ``checkpoint_every``   tiles between mid-range accumulator
                           checkpoints (0/None disables — a killed range
                           then restarts from its first row).
    ``ckpt_dir``           checkpoint root (None → a fresh temp dir per
                           engine, removed again by ``close()``).
    ``heartbeat_timeout``  seconds without a worker heartbeat before the
                           monitor declares it dead.  Staleness is
                           measured from the later of the worker's last
                           beat and the task's dispatch time, so an idle
                           pool between passes never goes stale.
    ``poll_interval``      monitor poll cadence in seconds.
    ``max_recoveries``     worker deaths tolerated per PASS (each
                           fan-out) before :class:`ClusterFailure`;
                           ``stats["recoveries"]`` still counts engine
                           lifetime totals.
    ``faults``             a :class:`~repro.cluster.faults.FaultPlan` (or
                           event list) injected into the worker loops.
    """

    num_workers: int = 2
    tile_rows: int | None = None
    checkpoint_every: int | None = 1
    ckpt_dir: str | None = None
    heartbeat_timeout: float = 10.0
    poll_interval: float = 0.01
    max_recoveries: int = 4
    faults: object = None

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError(f"need >= 1 worker, got {self.num_workers}")


_STOP = object()


class _Task:
    __slots__ = ("rng", "fn", "epoch", "status", "result", "error", "done",
                 "dispatched_at")

    def __init__(self, rng: RowRange, fn, epoch: int = 0):
        self.rng = rng
        self.fn = fn
        self.epoch = epoch
        self.status = "pending"
        self.result = None
        self.error = None
        self.done = threading.Event()
        self.dispatched_at = time.monotonic()  # re-stamped on submit


class _Worker:
    """One pool member: a thread draining an inbox of range tasks.

    A :class:`WorkerKilled` raised inside a task kills the THREAD — no
    cleanup, no further tasks, heartbeats stop — which is the preemption
    model the coordinator must recover from.
    """

    def __init__(self, wid: int):
        self.id = wid
        self.inbox: queue.Queue = queue.Queue()
        self.last_beat = time.monotonic()
        self.tasks: list[_Task] = []  # unfinished tasks queued to me
        self.thread = threading.Thread(
            target=self._loop, name=f"repro-cluster-w{wid}", daemon=True
        )
        self.thread.start()

    def beat(self):
        self.last_beat = time.monotonic()

    @property
    def thread_alive(self) -> bool:
        return self.thread.is_alive()

    def submit(self, task: _Task):
        task.dispatched_at = time.monotonic()
        self.tasks.append(task)
        self.inbox.put(task)

    def stop(self):
        self.inbox.put(_STOP)

    def _loop(self):
        while True:
            task = self.inbox.get()
            if task is _STOP:
                return
            if task.status == "abandoned":
                task.done.set()
                continue
            self.beat()
            try:
                task.result = task.fn(self)
                task.status = "done"
            except WorkerKilled as e:
                task.error = e
                task.status = "killed"
                task.done.set()
                return  # the whole worker dies, inbox abandoned
            except Exception as e:  # real bug: surfaced by the monitor
                task.error = e
                task.status = "error"
            task.done.set()


class ClusterEngine(RowSource):
    """Coordinator + worker pool over one row source (see module doc).

    Subclasses :class:`RowSource`, so an engine drops in anywhere a
    source does (``as_source`` passes it through unchanged) — the
    streaming drivers then discover its distributed ``cluster_sketch`` /
    ``matvec`` / ``rmatvec`` / ``residual_grad`` methods by probing.
    """

    # Checked by reprolint R1.  Worker threads and the coordinator both
    # write these; everything else (_workers, _dead, _next_id,
    # _pass_recoveries, _closed) is coordinator-thread-private by
    # construction and deliberately unlisted.
    GUARDED_BY = {
        "stats": "_lock",
        "_tile_counts": "_lock",
        "_submissions": "_lock",
        "_sketch_seq": "_lock",
    }

    def __init__(self, source, spec: ClusterSpec | None = None, *,
                 backend: str = "auto", counters: dict | None = None):
        self.source = as_source(source)
        self.spec = spec or ClusterSpec()
        self.shape = self.source.shape
        self.dtype = self.source.dtype
        self.backend = backend
        self.counters = counters  # optional external pass/tile counters
        self._grid = int(self.spec.tile_rows or self.source.tile_rows)
        self._plan = as_plan(self.spec.faults)
        self._owns_ckpt_dir = self.spec.ckpt_dir is None
        self._ckpt_dir = self.spec.ckpt_dir or tempfile.mkdtemp(
            prefix="repro-cluster-"
        )
        self._closed = False
        self._pass_recoveries = 0  # reset by every _execute fan-out
        self._workers: dict[int, _Worker] = {
            w: _Worker(w) for w in range(self.spec.num_workers)
        }
        self._dead: set[int] = set()
        self._next_id = self.spec.num_workers
        self._lock = make_lock("ClusterEngine._lock")  # counters + submissions
        self._ckpt_lock = make_lock("ClusterEngine._ckpt_lock")  # ckpt writes
        self._tile_counts: dict[tuple[int, str], int] = {}
        self._submissions: list = []
        self._sketch_seq = 0  # guards against zombie submissions from a
        # previous pass leaking into a later one
        self.stats = REGISTRY.stats_dict("cluster", {
            "workers": self.spec.num_workers,
            "recoveries": 0,
            "reassignments": 0,
            "respawns": 0,
            "restores": 0,
            "checkpoints": 0,
            "duplicates_dropped": 0,
            "heartbeat_evictions": 0,
            "passes": 0,
            "tiles": 0,
        })

    # ------------------------------------------------------- RowSource face
    @property
    def tile_rows(self) -> int:
        return self._grid

    @property
    def num_tiles(self) -> int:
        return -(-self.shape[0] // self._grid)

    def tiles(self):
        # serial fallback so the engine drops in anywhere a source does
        yield from self.source.tiles()

    @property
    def supports_random_access(self) -> bool:
        return self.source.supports_random_access

    def read_rows(self, offset, length):
        return self.source.read_rows(offset, length)

    @property
    def ckpt_dir(self) -> str:
        return self._ckpt_dir

    def close(self):
        """Stop the pool; idempotent.  A temp checkpoint dir the engine
        created for itself is removed with it (a caller-provided
        ``spec.ckpt_dir`` is left untouched)."""
        if self._closed:
            return
        self._closed = True
        for w in self._workers.values():
            w.stop()
        for w in self._workers.values():
            # bounded join: healthy workers exit on _STOP instantly;
            # an injected zombie may still be sleeping — don't hang on it
            w.thread.join(timeout=0.5)
        if self._owns_ckpt_dir:
            shutil.rmtree(self._ckpt_dir, ignore_errors=True)

    # ------------------------------------------------------------ plumbing
    def _live_ids(self) -> list[int]:
        return [
            w for w, wk in self._workers.items()
            if w not in self._dead and wk.thread_alive
        ]

    def _fault_gate(self, worker: _Worker, phase: str):
        worker.beat()  # starting a tile is life, even if it computes long
        with self._lock:
            k = (worker.id, phase)
            tile = self._tile_counts.get(k, 0)
            self._tile_counts[k] = tile + 1
        self._plan.before_tile(worker.id, phase, tile)

    def _count_tiles(self, k: int = 1):
        with self._lock:
            self.stats["tiles"] += k
            if self.counters is not None:
                self.counters["tiles"] += k

    def _count_pass(self):
        with self._lock:
            self.stats["passes"] += 1
            if self.counters is not None:
                self.counters["passes"] += 1

    def _recover(self, ownership: OwnershipMap, victim: int, make_fn,
                 pending: dict):
        """Declare ``victim`` dead and reassign its unfinished ranges."""
        obs_trace.instant("cluster.recover", victim=victim)
        with self._lock:
            self.stats["recoveries"] += 1
        self._pass_recoveries += 1
        if self._pass_recoveries > self.spec.max_recoveries:
            raise ClusterFailure(
                f"recovery budget exhausted ({self.spec.max_recoveries} "
                f"per pass); last casualty: worker {victim}"
            )
        self._dead.add(victim)
        wk = self._workers[victim]
        for t in wk.tasks:
            if not t.done.is_set():
                t.status = "abandoned"
        live = self._live_ids()
        if not live:
            nid = self._next_id
            self._next_id += 1
            self._workers[nid] = _Worker(nid)
            obs_trace.instant("cluster.respawn", worker=nid)
            with self._lock:
                self.stats["respawns"] += 1
            live = [nid]
            ownership.assignments.setdefault(nid, [])
        moves = ownership.reassign(victim, live)
        for tgt, rng in moves:
            obs_trace.instant(
                "cluster.reassign", range=(rng.start, rng.stop), to=tgt
            )
            with self._lock:
                self.stats["reassignments"] += 1
            task = _Task(rng, make_fn(rng), epoch=pending[rng].epoch + 1)
            pending[rng] = task
            self._workers[tgt].submit(task)

    def _execute(self, ranges: list[RowRange], make_fn) -> dict:
        """Run ``make_fn(rng)(worker)`` for every range on the pool with
        heartbeat monitoring and kill/timeout recovery.  Returns
        {range: result} once every range has completed somewhere."""
        if self._closed:
            raise ClusterFailure("engine is closed")
        self._pass_recoveries = 0
        live = self._live_ids()
        if not live:
            raise ClusterFailure("no live workers")
        ownership = OwnershipMap(
            m=self.shape[0], tile_rows=self._grid,
            assignments={w: [] for w in live},
        )
        pending: dict[RowRange, _Task] = {}
        for i, rng in enumerate(ranges):
            w = live[i % len(live)]
            ownership.assignments[w].append(rng)
            task = _Task(rng, make_fn(rng))
            pending[rng] = task
            self._workers[w].submit(task)
        results: dict[RowRange, object] = {}
        while any(rng not in results for rng in ranges):
            progressed = False
            for rng in ranges:
                if rng in results:
                    continue
                task = pending[rng]
                owner = ownership.owner_of(rng)
                if task.done.is_set() and task.status == "done":
                    results[rng] = task.result
                    if owner is not None:
                        self._workers[owner].tasks = [
                            t for t in self._workers[owner].tasks if t is not task
                        ]
                        ownership.assignments[owner].remove(rng)
                    progressed = True
                elif task.done.is_set() and task.status == "killed":
                    self._recover(ownership, owner, make_fn, pending)
                    progressed = True
                elif task.done.is_set() and task.status == "error":
                    raise task.error
                elif owner is not None:
                    wk = self._workers[owner]
                    # staleness from the later of the worker's last beat
                    # and this task's dispatch: a pool that sat idle
                    # between passes (or a queued task behind a long
                    # tile) is not dead, it just hasn't started yet
                    alive_ref = max(wk.last_beat, task.dispatched_at)
                    stale = (
                        time.monotonic() - alive_ref
                        > self.spec.heartbeat_timeout
                    )
                    if stale or not wk.thread_alive:
                        if stale and wk.thread_alive:
                            obs_trace.instant(
                                "cluster.eviction", worker=owner,
                                stale_s=time.monotonic() - alive_ref,
                            )
                            with self._lock:
                                self.stats["heartbeat_evictions"] += 1
                        self._recover(ownership, owner, make_fn, pending)
                        progressed = True
            if not progressed:
                time.sleep(self.spec.poll_interval)
        return results

    # -------------------------------------------------------------- pass 1
    def cluster_sketch(self, op, *, rhs=None, backend: str = "auto"):
        """Fan pass-1 sketching out over the pool → the finalized (s,
        ncols) sketch of [A | rhs].  The per-range partial accumulators
        are checkpointed mid-range, restored on reassignment, deduped,
        then merged associatively in range order."""
        m, n = self.shape
        ncols = n + (1 if rhs is not None else 0)
        dtype = jnp.dtype(self.dtype)
        ckpt_every = self.spec.checkpoint_every or 0
        # checkpoints are namespaced by (operator draw, rhs): leftovers in
        # a persistent ckpt_dir from a DIFFERENT draw or rhs restore None
        # (fresh start) instead of failing — or silently poisoning — the
        # new pass
        ns = cckpt.pass_namespace(op, rhs)
        self._count_pass()
        with self._lock:
            self._submissions = []
            self._sketch_seq += 1
            seq = self._sketch_seq

        def submit(rng, acc, wid):
            with self._lock:
                if self._sketch_seq == seq:
                    self._submissions.append((rng, acc, wid))

        def make_fn(rng):
            def fn(worker: _Worker):
                with obs_trace.span(
                    "cluster.task", phase="sketch", worker=worker.id,
                    start=rng.start, stop=rng.stop,
                ):
                    acc, wm = None, rng.start
                    if ckpt_every:
                        got = cckpt.restore_accumulator(
                            self._ckpt_dir, op, ncols,
                            range_start=rng.start, range_stop=rng.stop,
                            phase=ns, dtype=dtype, backend=backend,
                        )
                        if got is not None:
                            acc, wm = got
                            obs_trace.instant(
                                "cluster.restore", worker=worker.id,
                                watermark=wm, start=rng.start, stop=rng.stop,
                            )
                            with self._lock:
                                self.stats["restores"] += 1
                    if acc is None:
                        acc = make_accumulator(op, ncols, dtype=dtype,
                                               backend=backend)
                    sub = RowRangeSource(self.source, wm, rng.stop,
                                         tile_rows=self._grid)
                    since = 0
                    for local_o, tile in sub.tiles():
                        self._fault_gate(worker, "sketch")
                        gl = wm + local_o
                        tile = jnp.asarray(tile)
                        t = tile.shape[0]
                        if rhs is not None:
                            tile = jnp.concatenate(
                                [tile,
                                 rhs[gl : gl + t][:, None].astype(tile.dtype)],
                                axis=1,
                            )
                        acc.update(tile, gl)
                        worker.beat()
                        obs_trace.instant(
                            "cluster.heartbeat", worker=worker.id, row=gl
                        )
                        self._count_tiles()
                        since += 1
                        if (
                            ckpt_every and since >= ckpt_every
                            and gl + t < rng.stop
                        ):
                            with self._ckpt_lock:
                                cckpt.save_accumulator(
                                    self._ckpt_dir, acc, gl + t,
                                    range_start=rng.start,
                                    range_stop=rng.stop,
                                    phase=ns,
                                )
                            obs_trace.instant(
                                "cluster.checkpoint", worker=worker.id,
                                watermark=gl + t,
                            )
                            with self._lock:
                                self.stats["checkpoints"] += 1
                            since = 0
                    submit(rng, acc, worker.id)
                    if self._plan.duplicate_submission(worker.id):
                        submit(rng, acc, worker.id)  # the dedup guard's moment
                    return True
            return fn

        with obs_trace.span(
            "cluster.pass1", rows=m, workers=len(self._live_ids())
        ):
            ranges = self._partition()
            self._execute(ranges, make_fn)
            chosen: dict[RowRange, object] = {}
            with self._lock:
                submissions = list(self._submissions)
            for rng, acc, _wid in submissions:
                if rng in chosen:
                    with self._lock:
                        self.stats["duplicates_dropped"] += 1
                    continue
                chosen[rng] = acc
            covered = 0
            for rng in sorted(chosen):
                if rng.start != covered:
                    raise ClusterFailure(
                        f"pass-1 coverage gap at row {covered} "
                        f"(next range {rng})"
                    )
                covered = rng.stop
            if covered != m:
                raise ClusterFailure(f"pass-1 covered {covered} of {m} rows")
            with obs_trace.span("cluster.merge", ranges=len(chosen)):
                merged = merge_all([chosen[rng] for rng in sorted(chosen)])
                out = merged.finalize()
                obs_trace.maybe_block(out)
        # the pass succeeded: its mid-range checkpoints are spent — clear
        # them so a persistent ckpt_dir doesn't grow without bound
        if ckpt_every:
            shutil.rmtree(os.path.join(self._ckpt_dir, ns),
                          ignore_errors=True)
        return out

    def _partition(self) -> list[RowRange]:
        live = self._live_ids()
        if not live:
            raise ClusterFailure("no live workers")
        ranges = partition_rows(self.shape[0], len(live), self._grid)
        return [r for r in ranges if r.rows > 0]

    # -------------------------------------------------------------- pass 2
    def _map_ranges(self, per_range_fn, phase: str = "map"):
        """Fan a stateless per-range computation out and return the
        results in ascending range order (deterministic reduction)."""
        self._count_pass()

        def make_fn(rng):
            def fn(worker: _Worker):
                with obs_trace.span(
                    "cluster.task", phase=phase, worker=worker.id,
                    start=rng.start, stop=rng.stop,
                ):
                    sub = RowRangeSource(self.source, rng.start, rng.stop,
                                         tile_rows=self._grid)
                    return per_range_fn(rng, sub, worker)
            return fn

        with obs_trace.span(
            "cluster.pass2", phase=phase, workers=len(self._live_ids())
        ):
            ranges = self._partition()
            results = self._execute(ranges, make_fn)
            return [results[rng] for rng in sorted(ranges)]

    def matvec(self, x):
        """A @ x by per-range placement (exact — no cross-range sums)."""
        x = jnp.asarray(x)

        def per_range(rng, sub, worker):
            parts = []
            for _local_o, tile in sub.tiles():
                self._fault_gate(worker, "matvec")
                parts.append(matmul(jnp.asarray(tile), x))
                worker.beat()
                self._count_tiles()
            return jnp.concatenate(parts, axis=0)

        return jnp.concatenate(
            self._map_ranges(per_range, phase="matvec"), axis=0)

    def rmatvec(self, u):
        """Aᵀ @ u: per-range partial adjoint products summed in range
        order (fixed grouping ⇒ reproducible for a fixed worker set)."""
        u = jnp.asarray(u)
        n = self.shape[1]

        def per_range(rng, sub, worker):
            g = jnp.zeros((n,) + u.shape[1:], u.dtype)
            for local_o, tile in sub.tiles():
                self._fault_gate(worker, "matvec")
                tile = jnp.asarray(tile)
                gl = rng.start + local_o
                g = g + matmul(tile.T, u[gl : gl + tile.shape[0]])
                worker.beat()
                self._count_tiles()
            return g

        parts = self._map_ranges(per_range, phase="rmatvec")
        g = parts[0]
        for p in parts[1:]:
            g = g + p
        return g

    def residual_grad(self, b, x):
        """ONE fused distributed pass: (‖b − Ax‖² per column, Aᵀ(b − Ax))."""
        b = jnp.asarray(b)
        x = jnp.asarray(x)
        n = self.shape[1]

        def per_range(rng, sub, worker):
            g = jnp.zeros((n,) + b.shape[1:], b.dtype)
            rn2 = jnp.zeros(b.shape[1:], b.dtype)
            for local_o, tile in sub.tiles():
                self._fault_gate(worker, "matvec")
                tile = jnp.asarray(tile)
                gl = rng.start + local_o
                r_t = b[gl : gl + tile.shape[0]] - matmul(tile, x)
                g = g + matmul(tile.T, r_t)
                rn2 = rn2 + jnp.sum(r_t * r_t, axis=0)
                worker.beat()
                self._count_tiles()
            return rn2, g

        parts = self._map_ranges(per_range, phase="residual_grad")
        rn2 = parts[0][0]
        g = parts[0][1]
        for p_rn2, p_g in parts[1:]:
            rn2 = rn2 + p_rn2
            g = g + p_g
        return rn2, g
