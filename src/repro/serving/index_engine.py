"""The request half of the serving engine over AULID + its device mirror.

The ROADMAP north-star is serving heavy mixed traffic; the paper's headline
claim (§4.4, §5.3) is that AULID stays fast *under updates*.  The engine is
the piece that makes the JAX read path honor that claim (DESIGN.md §3): one
host insert never freezes out the device mirror until an O(n) rebuild.

There is one engine, :class:`~repro.serving.sharded_engine.ShardedIndexEngine`
(DESIGN.md §4, §9); a single index is served as its one-shard case
(``partition_bulkload(keys, pays, 1)``).  This module holds what does not
depend on the range partition: request admission, the step, the fused read
batches and the step phases (:class:`BaseIndexEngine`), and the per-shard
host state (:class:`IndexShard`).

Request flow per :meth:`BaseIndexEngine.step`:

1. drain the queue, partitioning into writes and reads (step-level
   consistency: every write queued before the step is visible to every read
   executed in it — the oracle the property tests assert against);
2. record writes in their shard's ``DeltaOverlay`` (results computed
   overlay-first) and host log, then dispatch the overlay pack's device
   merge — the device mirror itself is untouched;
3. execute all point reads as ONE fused device batch and scans as one batch
   per power-of-two scan-length bucket (mixed scan lengths share compiles;
   results slice to the requested count); behind the first read dispatch,
   while the device merges and reads, the host logs are applied to the host
   ``Aulid`` indexes (which journal the writes) — no read consults a host
   index (DESIGN.md §3);
4. compaction policy, with every unfrozen host log empty: once a shard's
   ``len(overlay) >= gamma * n`` its overlay is folded into a fresh snapshot
   via ``refresh_device_index`` (the journal fast path re-mirrors only
   touched leaf rows when no SMO happened) and cleared — mirroring AULID's
   own Adjust criterion of amortizing structural work against a fraction of
   covered data (paper §4.4).

Write semantics are unique-key upserts (``insert`` overwrites an existing
key's payload; ``delete`` removes the key) so host, overlay, and device views
agree under arbitrary interleavings — AULID's duplicate-key multiset remains
available on the host path directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import numpy as np

from ..core.aulid import Aulid
from ..core.delta_overlay import DeltaOverlay, next_pow2
from ..core.device_index import (DeviceIndex, build_device_index,
                                 refresh_device_index)

MIN_SCAN_BUCKET = 8

# phases of the engine step, each timed into ``phase_s[name]`` (reported by
# ``stats()`` as ``<name>_s``) and traced as the profiler span
# ``aulid.<name>``: the swap install on the request path, the per-write loop
# (routing, overlay record, results), the overlay-pack refresh, the read
# batches' dispatch / wait on the device / per-request unpacking, the host
# index update of the step's writes (run behind the first device dispatch),
# and the background compaction build (timed on its pool thread, added at
# install).  The whole step is ``step_seconds``.
PHASES = ("install", "write_apply", "write_host", "read_dispatch",
          "read_wait", "read_unpack", "write_index", "compact_build")


def phase_span(name: str):
    """Profiler span ``aulid.<name>``: a TraceMe on the host plane, so it
    shares the device planes' clock in a ``jax.profiler`` trace; near free
    while no trace is running."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(f"aulid.{name}")


# shared background-build pool of the double-buffered compaction path
# (DESIGN.md §11); one per process — builds are host-CPU + transfer bound and
# each engine serializes its own swaps, so a small pool suffices
_COMPACT_POOL = None


def compaction_executor():
    global _COMPACT_POOL
    if _COMPACT_POOL is None:
        import concurrent.futures
        _COMPACT_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="aulid-compact")
    return _COMPACT_POOL


def scan_bucket(count: int) -> int:
    """Power-of-two scan-length bucket: mixed scan workloads compile once per
    distinct bucket instead of once per distinct length; results are computed
    at the bucket size and sliced back to the requested count."""
    return max(MIN_SCAN_BUCKET, next_pow2(int(count)))


def pad_queries(keys: list[int]) -> np.ndarray:
    """Pad a read batch to the next power of two with u64-max sentinel keys
    (never found; results past the real count are discarded) so the jitted
    read path compiles once per size bucket, not once per batch size."""
    q = np.full(next_pow2(len(keys)), 0xFFFFFFFFFFFFFFFF, dtype=np.uint64)
    q[: len(keys)] = keys
    return q


@dataclasses.dataclass
class IndexRequest:
    rid: int
    op: str                    # "get" | "insert" | "delete" | "scan"
    key: int
    payload: int = 0
    count: int = 0             # scan length
    result: object = None      # get: payload|None; delete: bool; scan: pairs
    done: bool = False


@dataclasses.dataclass
class IndexShard:
    """Per-shard host state: host index, its mirror, the live and frozen
    write overlays, the host log and the compaction count (DESIGN.md §3
    lifecycle, §9 sharding).  The device copies — the stacked pools and the
    one overlay pack — are the engine's.

    ``pending`` is the host log: writes recorded in the overlay whose host
    index update has not run yet (DESIGN.md §3).  The engine applies it
    behind each step's device work (``apply_log``); while a background build
    is in flight (``frozen_overlay``, DESIGN.md §11) the pre-freeze overlay
    stays merged into reads, the host index is read-only, and the log waits
    for the swap.  A shard freezes or compacts only with an empty log."""
    idx: Aulid
    overlay: DeltaOverlay
    di: DeviceIndex
    compactions: int = 0
    frozen_overlay: Optional[DeltaOverlay] = None
    pending: list = dataclasses.field(default_factory=list)

    @classmethod
    def wrap(cls, idx: Aulid, gamma: float) -> "IndexShard":
        # capacity floor ~= compaction threshold: one jit shape per lifetime
        overlay = DeltaOverlay.for_threshold(gamma * max(idx.n_items, 1))
        return cls(idx=idx, overlay=overlay, di=build_device_index(idx))

    # ---------------------------------------------------------------- writes
    def record_write(self, op: str, key: int, payload: int = 0):
        """Record a write (unique-key upsert semantics, module docstring) in
        the live overlay, which reads see this step, and in the host log.
        Returns the request result (True / delete outcome), computed
        overlay-first: every logged write is also in the live overlay, so
        the served view answers without the host index's update."""
        self.pending.append((op, key, payload))
        if op == "insert":
            self.overlay.record_insert(key, payload)
            return True
        existed = self._key_live(key)
        self.overlay.record_delete(key)
        return existed

    def apply_write(self, op: str, key: int, payload: int = 0):
        """Record a write and apply it to the host index at once, unless a
        background compaction is in flight (``apply_log``)."""
        result = self.record_write(op, key, payload)
        self.apply_log()
        return result

    def apply_log(self) -> int:
        """Apply the host log to the host index in arrival order and empty
        it; returns the number of writes applied.  Applied writes journal
        and fold at the next compaction; the overlay already serves them,
        so the served view never moves.  While a background compaction is
        in flight the host index is read-only (the build thread is walking
        it): the log then waits for ``finish_swap`` / ``abort_swap``."""
        if self.frozen_overlay is not None:
            return 0
        log, self.pending = self.pending, []
        for op, key, payload in log:
            if op == "insert":
                if not self.idx.update(key, payload):
                    self.idx.insert(key, payload)
            else:
                self.idx.delete(key)
        return len(log)

    def _key_live(self, key: int) -> bool:
        """Whether ``key`` currently exists in the served view — the deferred
        twin of ``idx.delete``'s return value: live overlay, then frozen
        overlay, then the (frozen) host index."""
        for ov in (self.overlay, self.frozen_overlay):
            if ov is not None:
                ent = ov.get(key)
                if ent is not None:
                    return not ent[1]
        return self.idx.lookup(key) is not None

    # ------------------------------------------------------------ compaction
    def needs_compaction(self, gamma: float) -> bool:
        return len(self.overlay) >= gamma * max(self.idx.n_items, 1)

    def freeze(self, count: bool = True) -> DeltaOverlay:
        """Freeze the overlay for a double-buffered compaction (DESIGN.md
        §11): reads keep merging it over the old snapshot, writes move to a
        fresh spawn, and the host index is read-only until ``finish_swap``.
        Counted as this shard's compaction NOW (at the decision point), so
        compaction counters are deterministic across sync/async modes.
        Repartition builds reuse the same freeze window but are counted by
        the engine's split/merge counters instead (``count=False``)."""
        assert self.frozen_overlay is None, "compaction already in flight"
        assert not self.pending, "freeze with unapplied host writes"
        self.frozen_overlay = self.overlay
        self.overlay = self.frozen_overlay.spawn_empty()
        if count:
            self.compactions += 1
        return self.frozen_overlay

    def finish_swap(self, new_di: DeviceIndex) -> None:
        """Retire the frozen overlay and replay the pending log into the
        host index (the writes deferred while the build ran)."""
        self.di = new_di
        self.frozen_overlay = None
        self.apply_log()

    def abort_swap(self) -> None:
        """Roll back a freeze whose background build FAILED (DESIGN.md §12):
        the old mirror stays live, the pending log is replayed into the host
        index (no lost writes), and the frozen overlay's entries are folded
        back under the live overlay — they are in the host index but not in
        the old mirror, so they must stay overlay-visible until a later
        compaction succeeds.  The served view never moves."""
        assert self.frozen_overlay is not None, "no build in flight"
        frozen, self.frozen_overlay = self.frozen_overlay, None
        self.overlay.merge_under(frozen)
        self.apply_log()

    def compact(self) -> None:
        """Fold the overlay into a fresh snapshot and clear it (DESIGN.md §3).
        The device update is the owner engine's job (``restack_shard``), as
        is the refresh of the (now empty) overlay pack."""
        assert self.frozen_overlay is None, \
            "sync compact during in-flight compaction (drain first)"
        assert not self.pending, "compact with unapplied host writes"
        self.di = refresh_device_index(self.idx, self.di)
        self.overlay.clear()
        self.compactions += 1

    def overlay_live(self) -> int:
        """Upper bound on live served-overlay entries (scan ``ov_bound``):
        counts the frozen overlay too while a compaction is in flight."""
        n = len(self.overlay)
        if self.frozen_overlay is not None:
            n += len(self.frozen_overlay)
        return n


class BaseIndexEngine:
    """The request half of :class:`ShardedIndexEngine` (DESIGN.md §4):
    admission, ``step()``, the fused read batches and the phase timers.

    The read batches call the engine's jitted entry points (``self._lookup``
    / ``self._scan``) on its device operands (``_snap()`` / ``_ov()``); the
    step calls its write and compaction path (``_apply_write``,
    ``_after_writes``, ``_apply_host_logs``, ``_maybe_compact``) and its
    step-boundary hooks (``_begin_step`` / ``_end_step``)."""

    def __init__(self):
        self.queue: list[IndexRequest] = []
        self.next_rid = 0
        # serving stats
        self.steps = 0
        self.reads_served = 0
        self.writes_applied = 0
        # writes whose host-index update ran behind a device dispatch of
        # their own step (``_drain_host_logs``)
        self.writes_deferred = 0
        self._logs_due = False
        self.step_seconds: list[float] = []   # per-step latency (p99 source)
        self.phase_s = dict.fromkeys(PHASES, 0.0)
        # first-seen read specializations — static args (count bucket /
        # ov_bound / height) PLUS every device operand's shape, i.e. the
        # jit cache key: each new combo compiles a fresh read variant, so
        # benchmarks can tag the steps that paid a compile instead of
        # guessing from latency.  A restack invalidates every combo (pool
        # shapes changed); a swap install re-uses them (shapes kept).
        self._read_shapes: set[tuple] = set()
        self.read_shape_misses = 0

    @contextlib.contextmanager
    def _phase(self, name: str):
        """Time the block into ``phase_s[name]`` and trace it as the span
        ``aulid.<name>``. Request thread only: a pool-thread job times itself
        under ``phase_span`` and returns its seconds for the install to add."""
        t0 = time.perf_counter()
        try:
            with phase_span(name):
                yield
        finally:
            self.phase_s[name] += time.perf_counter() - t0

    def _note_read_shape(self, *statics) -> None:
        sig = tuple(sorted(
            (name, k, tuple(v.shape))
            for name, ops in (("snap", self._snap()), ("ov", self._ov()))
            for k, v in ops.items() if hasattr(v, "shape")))
        key = statics + (self._height(), sig)
        if key not in self._read_shapes:
            self._read_shapes.add(key)
            self.read_shape_misses += 1

    # ------------------------------------------------------------- admission
    def submit(self, op: str, key: int, payload: int = 0,
               count: int = 0) -> IndexRequest:
        assert op in ("get", "insert", "delete", "scan"), op
        req = IndexRequest(self.next_rid, op, int(key), int(payload),
                           int(count))
        self.next_rid += 1
        self.queue.append(req)
        return req

    def get(self, key: int) -> IndexRequest:
        return self.submit("get", key)

    def insert(self, key: int, payload: int) -> IndexRequest:
        return self.submit("insert", key, payload)

    def delete(self, key: int) -> IndexRequest:
        return self.submit("delete", key)

    def scan(self, key: int, count: int = 100) -> IndexRequest:
        return self.submit("scan", key, count=count)

    def _drain_host_logs(self) -> None:
        """Apply the step's host logs to the host indexes (phase
        ``write_index``), once per step: the read paths call it right after
        the step's first device dispatch, so the host work runs while the
        device merges and reads; ``step()`` calls it after the reads for a
        step without any."""
        if not self._logs_due:
            return
        self._logs_due = False
        with self._phase("write_index"):
            self.writes_deferred += self._apply_host_logs()

    # ------------------------------------------------------------- read path
    def _serve_gets(self, gets: list[IndexRequest]) -> None:
        import jax.numpy as jnp
        with self._phase("read_dispatch"):
            q = jnp.asarray(pad_queries([r.key for r in gets]))
            self._note_read_shape("get", q.shape[0])
            pay, found, _ = self._lookup(self._snap(), self._ov(), q,
                                         height=self._height())
        self._drain_host_logs()
        with self._phase("read_wait"):
            pay = np.asarray(pay)
            found = np.asarray(found)
        with self._phase("read_unpack"):
            for i, r in enumerate(gets):
                r.result = int(pay[i]) if bool(found[i]) else None
                r.done = True
        self.reads_served += len(gets)

    def _serve_scans(self, scans: list[IndexRequest]) -> None:
        import jax.numpy as jnp
        by_bucket: dict[int, list[IndexRequest]] = {}
        for r in scans:
            by_bucket.setdefault(scan_bucket(r.count or 100), []).append(r)
        # live-overlay bound (pow2-bucketed): the scan's unrolled leaf walk
        # scales with how full the overlay IS, not its padded capacity
        ov_bound = next_pow2(max(self._overlay_live(), MIN_SCAN_BUCKET))
        for bucket, grp in sorted(by_bucket.items()):
            with self._phase("read_dispatch"):
                q = jnp.asarray(pad_queries([r.key for r in grp]))
                self._note_read_shape("scan", q.shape[0], bucket, ov_bound)
                ks, ps, valid = self._scan(self._snap(), self._ov(), q,
                                           count=bucket,
                                           height=self._height(),
                                           ov_bound=ov_bound)
            self._drain_host_logs()
            with self._phase("read_wait"):
                ks, ps, valid = map(np.asarray, (ks, ps, valid))
            with self._phase("read_unpack"):
                for i, r in enumerate(grp):
                    n = min(int(valid[i].sum()), r.count or 100)
                    r.result = list(zip(ks[i][:n].tolist(),
                                        ps[i][:n].tolist()))
                    r.done = True
            self.reads_served += len(grp)

    # ------------------------------------------------------------------ step
    def step(self) -> int:
        """Drain the queue: record the writes (overlay + host log) and
        dispatch the pack merge, serve all reads as fused device batches
        with the host-log drain behind the first dispatch, then the
        compaction policy (DESIGN.md §3). Returns requests completed."""
        if not self.queue:
            return 0
        t0 = time.perf_counter()
        self._begin_step()
        batch, self.queue = self.queue, []
        writes = [r for r in batch if r.op in ("insert", "delete")]
        gets = [r for r in batch if r.op == "get"]
        scans = [r for r in batch if r.op == "scan"]
        self._logs_due = bool(writes)
        if writes:
            with self._phase("write_apply"):
                for r in writes:
                    self._apply_write(r)
            self._after_writes()
        if gets:
            self._serve_gets(gets)
        if scans:
            self._serve_scans(scans)
        if writes:
            self._drain_host_logs()
            # after the drain and the step's reads: a freeze hands the build
            # a host index holding every write its overlay retires, and no
            # read of the step shares it with a compaction's device update
            self._maybe_compact()
        self._end_step()
        self.steps += 1
        self.step_seconds.append(time.perf_counter() - t0)
        return len(batch)

    def run(self) -> int:
        done = 0
        while self.queue:
            done += self.step()
        return done

    # ----------------------------------------------------------------- stats
    def stats(self) -> dict:
        return {
            "steps": self.steps,
            "reads_served": self.reads_served,
            "writes_applied": self.writes_applied,
            "writes_deferred": self.writes_deferred,
            "read_shape_misses": self.read_shape_misses,
            **{f"{k}_s": v for k, v in self.phase_s.items()},
        }
