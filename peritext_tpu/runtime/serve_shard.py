"""Mesh-sharded serving: partition sessions across N universe shards.

PR 10's :class:`~peritext_tpu.runtime.serve.ServePlane` batches every
session into ONE universe behind one scheduler — one ingest lane is the
fleet's throughput ceiling, and every cohort launch sweeps the whole
``[R, C]`` device plane even when the batch target only advances a
fraction of the rows.  Collabs (PAPERS.md) makes the case that CRDT
serving scales by composing many small independent replication domains;
Eg-walker argues for keeping per-shard hot-path state small.  This module
is that tier: a :class:`ShardedServePlane` that

- **partitions sessions across N universe shards** (one
  :class:`TpuUniverse` + one deadline-aware :class:`ServePlane` scheduler
  per shard, so cohort launches on different shards proceed
  independently — per-launch device work scales with the SHARD width,
  not the fleet width);
- **places one shard per mesh slice**: each shard universe is pinned to
  the lead device of a slice :func:`peritext_tpu.parallel.mesh.
  mesh_slices` carves out of the device mesh (round-robin when shards
  outnumber devices), and a multi-device slice can optionally GSPMD-shard
  its universe's replica axis over the slice (``mesh_within_shard``);
- **pads shard widths into pow2 shape buckets** (``bucket="pow2"``, the
  default): a shard fronting n sessions runs a pow2(n)-wide universe
  (inert ``__pad…`` replicas carry no traffic), so unevenly-loaded
  shards still share ONE compiled program set process-wide and the
  fleet-wide jit cache stays bounded — ``serve.shard.<i>.
  compile_cache_{hit,miss}`` (plus the plane-global aggregate) is the
  measure;
- **wires cross-shard anti-entropy** through the existing pubsub/sync
  machinery: sessions declaring the same ``doc`` form a replication
  group with a shared gap-tolerant group log and a
  :class:`~peritext_tpu.runtime.pubsub.Publisher` — every client submit
  fans out live to the sibling sessions on other shards (through the
  ``pubsub_deliver`` chaos site, so drops/dups/reorders exercise each
  shard's causal admission gate), and :meth:`ShardedServePlane.
  anti_entropy` redelivers each member's missing contiguous suffix so
  replicas of the same document on different shards converge
  byte-identically (tests/test_serve_shard.py pins it under chaos,
  breaker fast-fail, and the degrade path; ``fuzz --serve --shards K``
  soaks it).

Byte-identity stays the hard wall: each session's concatenated patch
stream equals direct per-change ingest of exactly what that session was
handed (client submits + cross-shard deliveries), because every shard is
a full ServePlane with the same admission gate.

Manual mode (``start=False``) steps/drains every shard deterministically;
threaded mode runs one scheduler thread per shard, and the shards' launches
share one ``serve.launches_in_flight`` gauge.  :meth:`ShardedServePlane.
warm` compiles every shard's cohort programs on that shard's device(s),
the shards concurrently; :meth:`ShardedServePlane.open_sessions` opens
many sessions at once, each shard's universe built at its final width.
Env defaults: ``PERITEXT_SERVE_SHARDS`` (shard count), ``PERITEXT_SERVE_SHARD_BUCKET``
(``pow2`` | ``exact``), ``PERITEXT_SERVE_PLACEMENT`` (``rr`` | ``load`` —
new sessions round-robin or join the least-loaded shard),
``PERITEXT_ELASTIC=1`` (attach the SLO-driven autoscaler,
runtime/elastic.py), plus the per-shard planes' own ``PERITEXT_SERVE_*``
knobs.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from peritext_tpu.runtime import telemetry
from peritext_tpu.runtime.pubsub import Publisher
from peritext_tpu.runtime.serve import (
    LaunchGauge,
    ServePlane,
    ServeSession,
    Submission,
    _bucket_pow2,
    _env_int,
)

Change = Dict[str, Any]

_log = logging.getLogger(__name__)

BUCKET_POW2 = "pow2"
BUCKET_EXACT = "exact"
_BUCKETS = (BUCKET_POW2, BUCKET_EXACT)

PLACEMENT_RR = "rr"
PLACEMENT_LOAD = "load"
_PLACEMENTS = (PLACEMENT_RR, PLACEMENT_LOAD)


class ParkedSubmission:
    """Future handed to a client whose submit landed while its session was
    mid-migration (runtime/elastic.py).  The migration's commit (or
    rollback) replays the park buffer onto the surviving inner session and
    binds each wrapper to the real :class:`~peritext_tpu.runtime.serve.
    Submission`; ``result``/``done`` then delegate, so callers cannot tell
    a parked submit from a direct one."""

    __slots__ = ("_bound", "_sub", "_error")

    def __init__(self) -> None:
        self._bound = threading.Event()
        self._sub: Any = None
        self._error: Optional[BaseException] = None

    def _bind(self, sub: Any) -> None:
        self._sub = sub
        self._bound.set()

    def _reject(self, error: BaseException) -> None:
        self._error = error
        self._bound.set()

    def done(self) -> bool:
        if not self._bound.is_set():
            return False
        return True if self._error is not None else self._sub.done()

    def result(self, timeout: Optional[float] = None):
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        if not self._bound.wait(timeout):
            raise TimeoutError(
                f"parked submission still migrating after {timeout}s"
            )
        if self._error is not None:
            raise self._error
        remaining = (
            None if deadline is None else max(0.0, deadline - _time.monotonic())
        )
        return self._sub.result(timeout=remaining)


class _GroupLog:
    """Gap-tolerant per-document change log for cross-shard anti-entropy.

    Unlike :class:`~peritext_tpu.runtime.log.ChangeLog` (strictly
    sequential appends), submissions may arrive with causal gaps (chaotic
    delivery routed a suffix to one shard before its prefix): every
    change is held by ``(actor, seq)``, and redelivery hands out each
    actor's **contiguous** prefix beyond the receiver's clock — exactly
    what a shard's admission gate can use.  A same-key record that
    differs byte-for-byte is a forked actor history and must surface.
    """

    def __init__(self) -> None:
        self._by_key: Dict[Tuple[str, int], Change] = {}

    def record(self, change: Change) -> None:
        key = (change["actor"], change["seq"])
        prev = self._by_key.get(key)
        if prev is None:
            self._by_key[key] = change
        elif prev != change:
            raise ValueError(
                f"conflicting change recorded for {key}: forked actor history"
            )

    def contiguous(self, target_clock: Dict[str, int]) -> List[Change]:
        """Each actor's contiguous run of changes past ``target_clock``,
        in per-actor seq order (the shape ``ChangeLog.missing_changes``
        hands to the gate)."""
        out: List[Change] = []
        actors = sorted({a for a, _ in self._by_key})
        for actor in actors:
            seq = target_clock.get(actor, 0) + 1
            while (actor, seq) in self._by_key:
                out.append(self._by_key[(actor, seq)])
                seq += 1
        return out


class ShardSession:
    """One client session on the sharded plane: wraps the shard-local
    :class:`ServeSession` and, for ``doc``-grouped sessions, fans every
    client submit out to the sibling sessions on other shards."""

    def __init__(
        self,
        plane: "ShardedServePlane",
        inner: ServeSession,
        shard: int,
        doc: Optional[str],
    ) -> None:
        self._plane = plane
        self._inner = inner
        self.shard = shard
        self.doc = doc
        self.name = inner.name
        self.replica = inner.replica
        # Live-migration parking (runtime/elastic.py): non-None while a
        # migration of THIS session is mid-protocol — deliveries buffer
        # here and the commit/rollback replays them onto the surviving
        # inner session.  None on the hot path, so submit()/_deliver()
        # pay exactly one attribute check when elasticity is off.
        self._parked: Optional[List[Tuple[List[Change], Optional[ParkedSubmission]]]] = None
        # Document-lifecycle state (runtime/lifecycle.py): True while this
        # session's replica row is evicted to a durable checkpoint.  A
        # client submit to a cold session transparently hydrates it; cross-
        # shard deliveries to a cold session drop (the group log holds
        # them, and hydration replays the tail through the admission gate).
        self._cold = False

    @property
    def patch_log(self):
        return self._inner.patch_log

    def pending(self) -> int:
        return self._inner.pending()

    def submit(
        self,
        changes: Sequence[Change],
        wait: bool = False,
        timeout: Optional[float] = None,
    ):
        """Admit a client batch on this session's shard, then (for a
        ``doc`` group) record it in the group log and publish it to the
        sibling sessions on other shards — one change per publish, so
        per-link chaos (drop/dup/reorder) lands on each sibling's
        admission gate independently."""
        changes = list(changes)
        lc = self._plane.lifecycle
        cold = False
        if lc is not None and self._cold:
            # Hydrate-on-submit BEFORE recording: the hydration tail
            # replays whatever the logs already hold, so this batch must
            # not be logged yet — its patches belong to the Submission
            # future minted below, not to the anonymous tail replay.
            # ``pending=`` additionally excludes the batch from the tail
            # should it already be logged (a parked replay re-entering).
            lc.ensure_resident(self, pending=changes)
            cold = True
        if lc is not None and changes:
            # Lifecycle log + LRU touch BEFORE admission (mirrors the
            # group-log record-then-admit contract): a change must be
            # logged before any admission-side chaos can drop it, so a
            # later hydration can still replay it.
            lc._observe(self, changes)
        if self.doc is not None and changes:
            # Record into the group log BEFORE admission: a forked actor
            # history must reject loudly up front, never after the local
            # shard already accepted the submission.
            self._plane._record(self, changes)
        if self._parked is not None:
            sub = self._plane._park(self, changes)
        else:
            sub = self._inner.submit(changes)
            if lc is not None and isinstance(sub, Submission):
                sub.lat_class = "cold" if cold else "warm"
        if self.doc is not None and changes:
            self._plane._fan_out(self, changes)
        if wait:
            return sub.result(timeout=timeout)
        return sub

    def _deliver(self, changes: Sequence[Change]) -> None:
        """Cross-shard delivery entry (live fan-out, anti-entropy): drops
        while this session is evicted (the group log already holds the
        change — hydration replays the contiguous tail), parks during a
        migration of this session, else straight to the shard-local
        admission lane."""
        if self._cold:
            return
        if self._parked is not None:
            self._plane._park(self, list(changes), deliver=True)
            return
        self._inner.submit(changes)


class _Shard:
    """One shard slot: a lazily-created universe (first session brings it
    up on the shard's mesh slice) plus its ServePlane scheduler."""

    __slots__ = ("index", "devices", "universe", "plane", "real", "pad_ids", "pads_minted")

    def __init__(self, index: int) -> None:
        self.index = index
        # Mesh slice, resolved lazily at first universe creation (a
        # universe_factory plane never touches jax at all).
        self.devices: Optional[List[Any]] = None
        self.universe: Any = None
        self.plane: Optional[ServePlane] = None
        self.real: List[str] = []  # replicas fronted by sessions
        self.pad_ids: List[str] = []  # live inert pow2-bucket padding rows
        self.pads_minted = 0  # monotonic counter so dropped ids never reuse


class ShardedServePlane:
    """N universe shards behind one session-routing facade (see the
    module docstring).  ``shards`` defaults to ``PERITEXT_SERVE_SHARDS``;
    the per-shard scheduler knobs (batch target / deadline / quantum /
    on_open) pass straight through to each shard's :class:`ServePlane`."""

    def __init__(
        self,
        shards: Optional[int] = None,
        *,
        batch_target: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        quantum: Optional[int] = None,
        on_open: Optional[str] = None,
        start: bool = True,
        name: str = "serve",
        bucket: Optional[str] = None,
        placement: Optional[str] = None,
        capacity: int = 256,
        max_mark_ops: int = 64,
        universe_factory: Optional[Callable[[List[str], int], Any]] = None,
        devices: Optional[Sequence[Any]] = None,
        mesh_within_shard: bool = False,
    ) -> None:
        n = shards if shards is not None else _env_int("PERITEXT_SERVE_SHARDS", 1)
        if n < 1:
            raise ValueError(f"shards must be >= 1, got {n}")
        bucket = bucket or os.environ.get("PERITEXT_SERVE_SHARD_BUCKET", BUCKET_POW2)
        if bucket not in _BUCKETS:
            raise ValueError(
                f"unknown bucket policy {bucket!r}; known: {', '.join(_BUCKETS)}"
            )
        placement = placement or os.environ.get(
            "PERITEXT_SERVE_PLACEMENT", PLACEMENT_RR
        )
        if placement not in _PLACEMENTS:
            raise ValueError(
                f"unknown placement policy {placement!r}; "
                f"known: {', '.join(_PLACEMENTS)}"
            )
        self.name = name
        self.bucket = bucket
        self.placement = placement
        self._capacity = capacity
        self._max_mark_ops = max_mark_ops
        self._universe_factory = universe_factory
        self._mesh_within_shard = mesh_within_shard
        self._plane_kw = dict(
            batch_target=batch_target,
            deadline_ms=deadline_ms,
            quantum=quantum,
            on_open=on_open,
        )
        self._start = start
        self._gauge = LaunchGauge()
        self._lock = threading.RLock()
        self._devices = devices
        self._slices: Optional[List[List[Any]]] = None
        self.shards: List[_Shard] = [_Shard(i) for i in range(n)]
        self._sessions: Dict[str, ShardSession] = {}
        self._by_replica: Dict[str, ShardSession] = {}
        self._next_shard = 0
        # doc -> replication group: gap-tolerant log + live pubsub fan-out.
        self._docs: Dict[str, Dict[str, Any]] = {}
        # Fleet view on the ops surface (ISSUE 13): per-shard occupancy +
        # the compiled-shape pressure (the UNION across shards — equal
        # widths share programs).  The per-shard ServePlanes contribute
        # their own per-session "serve" entries.
        telemetry.register_status_source("serve_shards", self._status)
        if telemetry.enabled:
            telemetry.gauge("serve.shards", n)
        # SLO-driven autoscaler (ISSUE 17): PERITEXT_ELASTIC=1 attaches
        # the control loop; elastic.py takes the plane as an argument, so
        # no import cycle.  Off by default — the serving hot paths then
        # pay only the one _parked attribute check above.
        self.elastic: Any = None
        if os.environ.get("PERITEXT_ELASTIC", "") not in ("", "0"):
            from peritext_tpu.runtime.elastic import ElasticController

            self.elastic = ElasticController(self, start=start)
        # Multi-tenant document lifecycle (ISSUE 20): PERITEXT_LIFECYCLE=1
        # attaches the LRU evict/hydrate reaper; lifecycle.py takes the
        # plane as an argument, same no-cycle pattern as elastic.  Off by
        # default — submit/_deliver then pay one attribute check each.
        self.lifecycle: Any = None
        if os.environ.get("PERITEXT_LIFECYCLE", "") not in ("", "0"):
            from peritext_tpu.runtime.lifecycle import DocLifecycle

            self.lifecycle = DocLifecycle(self, start=start)

    def _status(self) -> Dict[str, Any]:
        with self._lock:
            shards: List[Dict[str, Any]] = []
            shapes: set = set()
            for shard in self.shards:
                if shard.plane is None:
                    shards.append({"shard": shard.index, "sessions": 0})
                    continue
                shapes |= shard.plane.shape_keys()
                # Per-shard pending is read under the INNER plane's lock
                # (facade-lock -> plane-lock, the established order): a
                # concurrent session() on that plane mutates _sessions
                # under the plane lock, and an unlocked dict iteration
                # here would intermittently blow up the whole status tick.
                with shard.plane._lock:
                    pending = sum(
                        s._pending for s in shard.plane._sessions.values()
                    )
                shards.append(
                    {
                        "shard": shard.index,
                        "sessions": len(shard.real),
                        "width": len(shard.universe.replica_ids),
                        "pads": len(shard.pad_ids),
                        "flushes": shard.plane.stats["flushes"],
                        "pending": pending,
                    }
                )
            return {
                "plane": self.name,
                "shards": shards,
                "doc_groups": len(self._docs),
                "fleet_compiled_shapes": len(shapes),
            }

    # -- shard provisioning --------------------------------------------------

    def _mint_pads(self, shard: _Shard, count: int) -> List[str]:
        ids = [
            f"__pad_{shard.index}_{shard.pads_minted + k}" for k in range(count)
        ]
        shard.pads_minted += count
        shard.pad_ids.extend(ids)
        return ids

    def _make_universe(self, shard: _Shard, replica_ids: List[str]) -> Any:
        if self._universe_factory is not None:
            return self._universe_factory(replica_ids, shard.index)
        from peritext_tpu.ops import TpuUniverse

        if self._slices is None:
            # First backend touch happens here, not at plane construction
            # (a factory-backed plane must stay jax-free).
            from peritext_tpu.parallel.mesh import mesh_slices

            self._slices = mesh_slices(len(self.shards), devices=self._devices)
        shard.devices = list(self._slices[shard.index])
        # One shard per mesh slice: the universe's state and every launch's
        # inputs live on the slice's lead device (a multi-device slice
        # additionally GSPMD-shards the replica axis below).
        return TpuUniverse(
            replica_ids,
            capacity=self._capacity,
            max_mark_ops=self._max_mark_ops,
            device=shard.devices[0],
        )

    def _reshard_slice(self, shard: _Shard) -> None:
        """GSPMD-shard the shard universe's replica axis over its mesh
        slice (opt-in; only when the width divides the slice — pow2
        buckets make that the steady state)."""
        if (
            not self._mesh_within_shard
            or shard.devices is None  # factory-backed: placement is the factory's
            or len(shard.devices) < 2
        ):
            return
        width = len(shard.universe.replica_ids)
        if width % len(shard.devices) != 0:
            return  # re-judged after the next width change
        from peritext_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(shard.devices, len(shard.devices), 1)
        shard.universe.shard(mesh, shard_seq=False)

    def _bring_up(self, shard: _Shard, replica_ids: List[str]) -> None:
        """Build a shard's universe over ``replica_ids`` and its plane."""
        shard.universe = self._make_universe(shard, replica_ids)
        shard.plane = ServePlane(
            shard.universe,
            start=self._start,
            name=f"{self.name}.shard{shard.index}",
            shard=shard.index,
            gauge=self._gauge,
            **self._plane_kw,
        )
        self._reshard_slice(shard)

    def _provision_locked(self, shard: _Shard, replica: str) -> None:
        """Bring ``replica`` up on ``shard``, holding the universe width
        EXACTLY to the bucket policy: pow2 width = pow2(real sessions),
        the inert pad rows making up the difference.  A real replica
        arriving while pads exist consumes one (drop pad + add real, so
        the width — and therefore the compiled program shape — does not
        move); past the bucket boundary the width doubles and fresh pads
        fill it.  On a running shard the mutation runs under the plane's
        flush quiescence barrier — ``add/drop_replicas`` rebuild the
        device state a concurrent launch would be reading."""
        shard.real.append(replica)
        target = (
            _bucket_pow2(len(shard.real))
            if self.bucket == BUCKET_POW2
            else len(shard.real)
        )
        if shard.universe is None:
            self._bring_up(shard, [replica] + self._mint_pads(shard, target - 1))
            return

        def mutate() -> None:
            if shard.pad_ids:
                # Common case inside a bucket: hand a pad row to the
                # joining session — pure bookkeeping, width (and the
                # compiled program shape) pinned, no state rebuild.
                shard.universe.rename_replica(shard.pad_ids.pop(), replica)
                return
            width = len(shard.universe.replica_ids)
            grow = [replica]
            if target > width + 1:
                grow += self._mint_pads(shard, target - width - 1)
            shard.universe.add_replicas(grow)
            self._reshard_slice(shard)

        shard.plane.run_quiesced(mutate)

    def _bucket_target(self, shard: _Shard) -> int:
        return max(
            1,
            _bucket_pow2(len(shard.real))
            if self.bucket == BUCKET_POW2
            else len(shard.real),
        )

    def _evacuate_locked(self, shard: _Shard, replica: str) -> None:
        """Remove a migrated-away real replica from its shard, holding the
        width to the bucket policy — the inverse of
        :meth:`_provision_locked`.  ``drop_replicas`` refuses to empty a
        universe, so a lone row swaps for a fresh pad instead; excess pads
        past the (possibly shrunken) bucket drop with the row in ONE
        gather where they can."""
        shard.real.remove(replica)
        target = self._bucket_target(shard)

        def mutate() -> None:
            uni = shard.universe
            width = len(uni.replica_ids)
            if target > width - 1:
                # Dropping the row would under-shoot the bucket (or empty
                # the universe): pad up first so the drop lands on-width.
                uni.add_replicas(self._mint_pads(shard, target - (width - 1)))
            drop = [replica]
            excess = len(uni.replica_ids) - 1 - target
            while excess > 0 and shard.pad_ids:
                drop.append(shard.pad_ids.pop())
                excess -= 1
            uni.drop_replicas(drop)
            self._reshard_slice(shard)

        shard.plane.run_quiesced(mutate)

    def _unprovision_locked(self, shard: _Shard, replica: str) -> None:
        """Roll a provisioned-but-unbound replica row back out (migration
        rollback): an untouched row rebinds to a fresh pad (width pinned,
        zero device work); a row the failed import already wrote drops the
        hard way."""
        shard.real.remove(replica)

        def mutate() -> None:
            uni = shard.universe
            i = uni.index_of[replica]
            if not uni.clocks[i]:
                uni.rename_replica(replica, self._mint_pads(shard, 1)[0])
            else:
                uni.add_replicas(self._mint_pads(shard, 1))
                uni.drop_replicas([replica])
            # Trim pads past the bucket so rollback restores the exact
            # pre-provision width (compiled-shape pressure unchanged).
            target = self._bucket_target(shard)
            drop: List[str] = []
            while len(uni.replica_ids) - len(drop) > target and shard.pad_ids:
                drop.append(shard.pad_ids.pop())
            if drop:
                uni.drop_replicas(drop)
            self._reshard_slice(shard)

        shard.plane.run_quiesced(mutate)

    # -- sessions ------------------------------------------------------------

    def session(
        self,
        name: str,
        replica: str,
        *,
        doc: Optional[str] = None,
        shard: Optional[int] = None,
        **session_kw: Any,
    ) -> ShardSession:
        """Open a session fronting ``replica`` on a shard (explicit
        ``shard=`` pins it; the default round-robins across shards so
        load — and a doc group's members — spread over the fleet).
        ``doc`` names the replication group for cross-shard anti-entropy;
        the remaining kwargs are :meth:`ServePlane.session`'s."""
        if self.lifecycle is not None:
            # Capacity-pressure eviction BEFORE the facade lock (the evict
            # protocol takes it): admitting this session must not push the
            # resident population past the lifecycle watermark.
            self.lifecycle._admission_pressure(exclude=name)
        with self._lock:
            if name in self._sessions:
                raise ValueError(f"session {name!r} already exists")
            if replica in self._by_replica:
                raise ValueError(
                    f"replica {replica!r} is already fronted by session "
                    f"{self._by_replica[replica].name!r}"
                )
            if shard is None:
                if self.placement == PLACEMENT_LOAD:
                    shard = self._least_loaded_locked()
                else:
                    shard = self._next_shard
                    self._next_shard = (self._next_shard + 1) % len(self.shards)
            if not (0 <= shard < len(self.shards)):
                raise ValueError(
                    f"shard {shard} out of range [0, {len(self.shards)})"
                )
            slot = self.shards[shard]
            self._provision_locked(slot, replica)
            inner = slot.plane.session(name, replica, **session_kw)
            sess = ShardSession(self, inner, shard, doc)
            self._sessions[name] = sess
            self._by_replica[replica] = sess
            if doc is not None:
                group = self._docs.get(doc)
                if group is None:
                    group = self._docs[doc] = {
                        "log": _GroupLog(),
                        "publisher": Publisher(),
                        "members": [],
                    }
                group["members"].append(sess)
                # Deliveries route through _deliver so a mid-migration
                # sibling parks them instead of racing the row handoff.
                group["publisher"].subscribe(
                    name, lambda change, s=sess: s._deliver([change])
                )
            if self.lifecycle is not None:
                self.lifecycle._admitted(sess)
            if telemetry.enabled:
                telemetry.gauge("serve.sessions", len(self._sessions))
                telemetry.counter(f"serve.shard.{shard}.sessions")
        return sess

    def open_sessions(
        self, specs: Sequence[Tuple[str, str]], **session_kw: Any
    ) -> List[ShardSession]:
        """Open one session per ``(name, replica)`` in ``specs``, placed as
        that many :meth:`session` calls in turn would place them, with the
        same result: the same shards, each shard's universe at the same
        pow2 width with its real replicas in the same order.  A shard that
        has no universe yet gets it built once at its final width (one
        state allocation, not a doubling per bucket boundary); any other
        shard, a ``doc``-grouped session, or an attached lifecycle or
        autoscaler takes the one-at-a-time path.  ``session_kw`` are
        :meth:`ServePlane.session`'s."""
        if self.lifecycle is not None or self.elastic is not None or "doc" in session_kw:
            return [self.session(n, r, **session_kw) for n, r in specs]
        with self._lock:
            names = [n for n, _ in specs]
            seen_n: set = set(self._sessions)
            seen_r: set = set(self._by_replica)
            for n, r in specs:
                if n in seen_n:
                    raise ValueError(f"session {n!r} already exists")
                if r in seen_r:
                    raise ValueError(f"replica {r!r} is already fronted")
                seen_n.add(n)
                seen_r.add(r)
            placed: Dict[int, List[Tuple[str, str]]] = {}
            loads = [self._shard_load_locked(s) for s in self.shards]
            sizes = [len(s.real) for s in self.shards]
            for spec in specs:
                if self.placement == PLACEMENT_LOAD:
                    i = min(range(len(self.shards)), key=lambda j: (loads[j], sizes[j], j))
                else:
                    i = self._next_shard
                    self._next_shard = (self._next_shard + 1) % len(self.shards)
                loads[i] += 1
                sizes[i] += 1
                placed.setdefault(i, []).append(spec)
            out: Dict[str, ShardSession] = {}
            for i, group in placed.items():
                slot = self.shards[i]
                if slot.universe is None:
                    slot.real.extend(r for _, r in group)
                    width = self._bucket_target(slot)
                    self._bring_up(
                        slot, slot.real + self._mint_pads(slot, width - len(slot.real))
                    )
                else:
                    for _, r in group:
                        self._provision_locked(slot, r)
                for n, r in group:
                    inner = slot.plane.session(n, r, **session_kw)
                    sess = out[n] = ShardSession(self, inner, i, None)
                    self._sessions[n] = sess
                    self._by_replica[r] = sess
                if telemetry.enabled:
                    telemetry.counter(f"serve.shard.{i}.sessions", len(group))
            if telemetry.enabled:
                telemetry.gauge("serve.sessions", len(self._sessions))
        return [out[n] for n in names]

    # -- placement + load ----------------------------------------------------

    def _shard_load_locked(self, shard: _Shard) -> int:
        """One shard's admission load: pending (admitted, unapplied)
        changes across its lanes plus its session count — the tie-break
        metric placement and the autoscaler agree on."""
        if shard.plane is None:
            return 0
        with shard.plane._lock:
            pending = sum(s._pending for s in shard.plane._sessions.values())
        return pending + len(shard.real)

    def _least_loaded_locked(self) -> int:
        """The ``load`` placement policy: the shard minimizing (load,
        sessions, index) — deterministic, and biased toward genuinely
        empty shards over merely-idle ones."""
        return min(
            range(len(self.shards)),
            key=lambda i: (
                self._shard_load_locked(self.shards[i]),
                len(self.shards[i].real),
                i,
            ),
        )

    # -- migration parking (runtime/elastic.py) ------------------------------

    def _park(
        self,
        sess: ShardSession,
        changes: List[Change],
        deliver: bool = False,
    ):
        """Buffer a delivery that raced a live migration.  Re-checks the
        parked flag under the facade lock: a caller that read a stale flag
        after the migration already unparked routes straight to the (by
        then rebound) inner session instead of stranding the changes in a
        dead buffer."""
        with self._lock:
            if sess._parked is not None:
                wrapper = None if deliver else ParkedSubmission()
                sess._parked.append((changes, wrapper))
                if telemetry.enabled:
                    telemetry.counter("elastic.parked_deliveries")
                return wrapper
            cold = sess._cold
        if cold:
            # The protocol that parked us committed an EVICTION while this
            # call raced it: a delivery simply drops (the group log holds
            # it for the hydration tail); a client submit hydrates and
            # admits like any other cold submit.
            if deliver:
                return None
            if self.lifecycle is not None:
                # pending=: this batch is already in the logs (recorded
                # before parking), but its patches belong to the future
                # minted just below — keep it out of the hydration tail.
                self.lifecycle.ensure_resident(sess, pending=changes)
        return sess._inner.submit(changes)

    # -- cross-shard anti-entropy --------------------------------------------

    def _record(self, sess: ShardSession, changes: List[Change]) -> None:
        group = self._docs[sess.doc]
        with self._lock:
            for change in changes:
                group["log"].record(change)

    def _fan_out(self, sess: ShardSession, changes: List[Change]) -> None:
        """Live cross-shard delivery, best-effort by design: the change
        is already durably in the group log and admitted on its home
        shard, so a failing link (chaos fail/wedge, a sibling's
        backpressure rejection) must never surface to the submitter or
        void its future — anti-entropy redelivers what the live fan-out
        missed.  A failed publish skips that change's remaining siblings
        (Publisher fans per change); later changes still go out."""
        group = self._docs[sess.doc]
        if telemetry.enabled:
            telemetry.counter("serve.fanout_changes", len(changes))
        for change in changes:
            try:
                group["publisher"].publish(sess.name, change)
            except Exception:
                if telemetry.enabled:
                    telemetry.counter("serve.fanout_failures")
                _log.warning(
                    "doc group %r: live fan-out of (%s, %s) from %s failed; "
                    "anti-entropy will redeliver",
                    sess.doc, change.get("actor"), change.get("seq"),
                    sess.name, exc_info=True,
                )

    def anti_entropy(self) -> int:
        """Redeliver every doc-group member's missing contiguous suffix
        from the group log (fault-free, dedup-idempotent — the shard
        gates drop what already landed).  Returns the number of changes
        redelivered; callers drain afterwards.

        Locking: membership snapshots under the facade lock; each
        member's universe clock is then read through its own plane's
        flush-quiescence barrier with NO facade lock held (one shard's
        slow or wedged launch must not stall submits fleet-wide), and the
        group-log read retakes the facade lock briefly.  No lock is ever
        nested inside another here, so no ordering constraint arises; a
        clock read racing a later submit only redelivers changes the
        gate will drop."""
        with self._lock:
            groups = [(g, list(g["members"])) for g in self._docs.values()]
        pending: List[Tuple[ShardSession, List[Change]]] = []
        for group, members in groups:
            for sess in members:
                if sess._parked is not None or sess._cold:
                    # Mid-migration: the commit replays the group-log tail
                    # itself; redelivering here would race the row handoff.
                    # Cold (evicted): the row is gone — hydration replays
                    # the tail, and redelivering would just rehydrate it.
                    continue
                shard = self.shards[sess.shard]
                if shard.plane is None:
                    continue
                try:
                    clock = shard.plane.run_quiesced(
                        lambda s=shard, r=sess.replica: s.universe.clock(r)
                    )
                except KeyError:
                    # The row moved shards between the membership snapshot
                    # and this read; the next pass sees the new home.
                    continue
                with self._lock:
                    missing = group["log"].contiguous(clock)
                if missing:
                    pending.append((sess, missing))
        redelivered = 0
        for sess, missing in pending:
            sess._deliver(missing)
            redelivered += len(missing)
        if redelivered and telemetry.enabled:
            telemetry.counter("serve.anti_entropy_changes", redelivered)
        return redelivered

    # -- driving -------------------------------------------------------------

    def _planes(self) -> List[ServePlane]:
        return [s.plane for s in self.shards if s.plane is not None]

    def step(self) -> bool:
        """Manual mode: one cohort-formation step on every shard.
        Returns True when any shard flushed."""
        worked = False
        for plane in self._planes():
            worked = plane.step() or worked
        return worked

    def drain(self, max_steps: int = 1000) -> int:
        """Manual mode: flush every shard until all lanes empty or no
        shard can progress.  Returns still-pending submissions fleet-wide
        (0 = fully drained).  Shard drains are independent: cross-shard
        fan-out happens at submit time, never during a flush, so one
        shard's flush can never unblock another's deferred lane."""
        return sum(plane.drain(max_steps) for plane in self._planes())

    def warm(self) -> int:
        """:meth:`ServePlane.warm` on every shard, each on its own device(s)
        and all at once (one thread a shard: compiles for different
        devices are separate programs and run side by side).  Returns the
        number of programs run across the shards."""
        planes = self._planes()
        counts: List[int] = [0] * len(planes)
        errors: List[BaseException] = []

        def one(k: int) -> None:
            try:
                counts[k] = planes[k].warm()
            except BaseException as exc:  # re-raised on the caller's thread
                errors.append(exc)

        threads = [
            threading.Thread(target=one, args=(k,), name=f"peritext-warm-{k}")
            for k in range(len(planes))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return sum(counts)

    def flush_and_wait(self, timeout: float = 30.0) -> None:
        for plane in self._planes():
            plane.flush_and_wait(timeout=timeout)

    def close(self, reject_pending: bool = True) -> None:
        if self.lifecycle is not None:
            self.lifecycle.close()
        if self.elastic is not None:
            self.elastic.close()
        for plane in self._planes():
            plane.close(reject_pending=reject_pending)

    def __enter__(self) -> "ShardedServePlane":
        return self

    def __exit__(self, *exc: Any) -> bool:
        self.close()
        return False

    # -- introspection -------------------------------------------------------

    def shard_of(self, replica: str) -> int:
        return self._by_replica[replica].shard

    def universe_of(self, replica: str) -> Any:
        return self.shards[self.shard_of(replica)].universe

    def clock(self, replica: str) -> Dict[str, int]:
        return self.universe_of(replica).clock(replica)

    def spans(self, replica: str) -> List[Dict[str, Any]]:
        """One replica's formatted spans, routed through its shard."""
        uni = self.universe_of(replica)
        return uni.spans(replica)

    @property
    def stats(self) -> Dict[str, Any]:
        """Fleet aggregate of the per-shard plane stats, plus the
        per-shard list under ``"shards"`` and the fleet-wide distinct
        compiled-shape count (shards of equal width share programs, so
        the union — not the sum — is the jit-cache pressure)."""
        agg: Dict[str, Any] = {}
        per_shard: List[Dict[str, int]] = []
        shapes: set = set()
        for shard in self.shards:
            if shard.plane is None:
                per_shard.append({})
                continue
            per_shard.append(dict(shard.plane.stats))
            shapes |= shard.plane.shape_keys()
            for key, val in shard.plane.stats.items():
                agg[key] = agg.get(key, 0) + val
        agg["shards"] = per_shard
        agg["fleet_compiled_shapes"] = len(shapes)
        return agg
