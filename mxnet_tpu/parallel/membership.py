"""Elastic-fleet membership: heartbeat leases, host-loss detection, and
the automatic re-form protocol (ROADMAP direction #5).

The reference's scale story delegated liveness to ps-lite (the dmlc
tracker restarting dead workers, server-side replication — SURVEY.md
§2.3); the TPU-native stack has no parameter server, and before this
module a dead host simply wedged every survivor inside the next
collective or barrier until the DCN timeout — checkpoint/restart with an
operator watching.  This module is the difference between that and a
fleet that holds an SLO unattended:

- **Leases** — every host publishes a monotonically-advancing heartbeat
  sequence over the same coordination-service KV store the tiered
  collectives already ride (:func:`~mxnet_tpu.parallel.dist.kv_publish`
  gen-stamped keys).  Liveness is judged on the OBSERVER's monotonic
  clock (a lease is dead when its sequence has not advanced for
  ``MXTPU_ELASTIC_LEASE_TTL`` seconds) — no cross-host clock trust.
- **Reaper/watcher** — a daemon thread on every host scans the lease
  table at the heartbeat cadence, flags expired members, notices
  peer-initiated re-form rounds, and detects this host's own fencing.
- **Re-form** — survivors run a deterministic KV consensus round (no
  device collective — the group is broken): each publishes its view of
  the surviving set, the lowest surviving rank leads, computes the
  member intersection, publishes the plan, collects acks, and commits a
  bumped **fencing generation**.  Every survivor then installs the
  narrowed group (:func:`~mxnet_tpu.parallel.dist.set_active_members`:
  new world size, contiguous logical ranks), the leader purges the dead
  hosts' KV generations, and a rejoin barrier over the survivors closes
  the round.
- **Fencing** — the false-death/split-brain case: a host whose
  heartbeat publisher stalled (GC pause, swap storm, the
  ``heartbeat_stall`` fault) but which keeps stepping is excluded by
  the reaper like any dead host.  The committed epoch record carries
  the bumped fence generation and the member list; the stalled host's
  watcher discovers a fence that excludes it and raises
  :class:`HostFenced` — it must exit, not rejoin, because the survivors
  have already re-formed without it and its KV generations were purged.

The supervised-training integration lives in
:class:`~mxnet_tpu.parallel.resilience.ResilientTrainer`: its membership
watcher quiesces at the next step boundary, calls :meth:`reform`,
restores the last committed checkpoint, re-winds the (re-sharded) data
loader, and raises the *recoverable* :class:`FleetReformed` so the
training loop rebuilds its epoch iterator and continues — no operator
action.

Everything here is observable: ``dist.membership.*`` metrics (alive /
world / fence gauges, heartbeat / expired / reform / fenced counters,
re-form latency histogram) and a flight-recorder membership ring
carrying the detect → quiesce → reform → resume timeline into crash
dumps.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from ..base import MXNetError, get_env
from ..faults import Deadline, DeadlineExceeded
from ..observability import tracing as _tracing
from ..observability.flight import recorder as _flight_recorder
from ..observability.registry import registry as _metrics_registry
from . import _coordination, dist

__all__ = ["MembershipManager", "LeaseTracker", "ReformResult",
           "FleetReformed", "HostFenced", "FleetLost",
           "MEMBER_PREFIX", "LEASE_PREFIX", "EPOCH_KEY"]

MEMBER_PREFIX = "mxtpu/member"
LEASE_PREFIX = f"{MEMBER_PREFIX}/lease"
# the committed epoch record lives in its OWN directory so the watcher's
# per-tick existence probe dir-gets at most one entry instead of the
# whole member namespace (every lease generation + reform-round key)
EPOCH_DIR = f"{MEMBER_PREFIX}/epoch"
EPOCH_KEY = f"{EPOCH_DIR}/record"
#: per-rank KV namespaces the reaper purges for a dead host, beyond its
#: lease: the allgather generation keys and the fleet metric-gather
#: stream (kv_publish shape) — a dead host's frozen state must never be
#: served to a later collect
PURGE_PREFIXES = ("mxtpu/fleet", LEASE_PREFIX)


class FleetReformed(MXNetError):
    """Recoverable: the fleet lost host(s), the survivors re-formed at
    the new world size, and training state was restored from the last
    committed checkpoint.  Raised at a step boundary by
    ``ResilientTrainer``; catch it at the epoch loop, rebuild the data
    iterator (the shard assignment changed), and continue training."""

    def __init__(self, result: "ReformResult", message: str):
        super().__init__(message)
        self.result = result


class HostFenced(MXNetError):
    """THIS host was declared dead by the surviving fleet (its lease
    expired — real death's twin is a stalled heartbeat publisher on a
    live process) and the membership epoch has moved past it.  The only
    safe action is to exit: the survivors already re-formed without
    this host and purged its KV generations; continuing to step or
    publish would be split-brain."""


class FleetLost(MXNetError):
    """The fleet cannot re-form: the coordination service is gone
    (coordinator host loss is fate-sharing — the KV store dies with
    it), no survivors remain, or the consensus round timed out.
    Unattended recovery is impossible; restart the job and let
    auto-resume pick up the last committed checkpoint."""


class ReformResult(NamedTuple):
    """What one committed re-form round decided."""
    fence: int                      # the bumped fencing generation
    old_members: Tuple[int, ...]    # active set before the round
    members: Tuple[int, ...]        # surviving ORIGINAL process ids
    dead: Tuple[int, ...]           # ranks fenced out by this round
    new_rank: int                   # this host's new contiguous rank
    new_world: int                  # the new world size
    resumed_t: Optional[int] = None  # checkpoint step restored (set by
    #                                 the resilience layer)
    timeline: Tuple = ()            # ((phase, wall_ts), ...) for the
    #                                 flight recorder


class LeaseTracker:
    """Pure lease-expiry accounting on the observer's clock.

    ``observe(rank, seq, now)`` feeds one scan's view of a peer's
    heartbeat sequence; a lease is **expired** when its sequence has not
    advanced for ``ttl`` seconds since the observer last saw it change
    (a peer never seen at all ages from the moment tracking started —
    ``track(rank, now)`` — so a host that dies before its first
    heartbeat is still reaped).  No wall-clock, no cross-host time:
    callers pass ``time.monotonic()`` and tests pass synthetic clocks.
    """

    def __init__(self, ttl: float):
        if ttl <= 0:
            raise MXNetError(f"lease ttl must be > 0, got {ttl}")
        self.ttl = float(ttl)
        self._last: Dict[int, Tuple[Optional[int], float]] = {}

    def track(self, rank: int, now: float) -> None:
        """Start aging ``rank`` (no-op if already tracked)."""
        self._last.setdefault(int(rank), (None, float(now)))

    def forget(self, rank: int) -> None:
        self._last.pop(int(rank), None)

    def observe(self, rank: int, seq: int, now: float) -> bool:
        """Feed one scan's sequence for ``rank``; returns True when the
        lease ADVANCED (fresh heartbeat since the last scan)."""
        rank, seq = int(rank), int(seq)
        prev = self._last.get(rank)
        if prev is not None and prev[0] is not None and seq <= prev[0]:
            return False
        self._last[rank] = (seq, float(now))
        return True

    def age(self, rank: int, now: float) -> Optional[float]:
        """Seconds since ``rank``'s lease last advanced (None if not
        tracked)."""
        entry = self._last.get(int(rank))
        if entry is None:
            return None
        return float(now) - entry[1]

    def expired(self, now: float,
                ranks: Optional[Iterable[int]] = None) -> List[int]:
        """Tracked ranks whose lease has not advanced within ttl."""
        pool = self._last.keys() if ranks is None else \
            [r for r in ranks if r in self._last]
        return sorted(r for r in pool
                      if float(now) - self._last[r][1] > self.ttl)


class MembershipManager:
    """One host's view of fleet membership: heartbeat publisher, lease
    reaper, fence discovery, and the re-form consensus protocol.

    Requires an initialized process group.  ``start()`` publishes the
    first lease synchronously (peers must see this host before its
    first interval elapses) and launches the publisher + watcher
    daemons; ``stop()`` tears both down.  The training-loop surface is
    three calls, all step-boundary cheap:

    - :meth:`raise_if_fenced` — surface this host's own fencing;
    - :attr:`reform_needed` — True once the reaper holds suspects (or a
      peer opened a re-form round);
    - :meth:`reform` — run the consensus round; returns a
      :class:`ReformResult` once the re-formed group is installed.

    ``step_barrier`` is the per-step lockstep sync a dead host breaks
    *quickly*: bounded at ~2 lease TTLs, it raises ``DeadlineExceeded``
    long before ``MXTPU_DIST_TIMEOUT`` would, and the resilience layer
    routes that into a forced lease scan and the re-form arc.
    """

    #: poll cadence inside the re-form round's wait loops
    _POLL_S = 0.05

    def __init__(self, *, lease_ttl: Optional[float] = None,
                 heartbeat_interval: Optional[float] = None,
                 reform_timeout: Optional[float] = None):
        if not dist.is_initialized():
            raise MXNetError(
                "MembershipManager requires an initialized process group "
                "(init_process_group) — leases ride the coordination-"
                "service KV store")
        self.lease_ttl = float(lease_ttl if lease_ttl is not None
                               else get_env("MXTPU_ELASTIC_LEASE_TTL"))
        self.heartbeat_interval = float(
            heartbeat_interval if heartbeat_interval is not None
            else get_env("MXTPU_ELASTIC_HEARTBEAT"))
        self.reform_timeout = float(
            reform_timeout if reform_timeout is not None
            else get_env("MXTPU_ELASTIC_REFORM_TIMEOUT"))
        if self.lease_ttl <= self.heartbeat_interval:
            raise MXNetError(
                f"lease ttl ({self.lease_ttl}s) must exceed the "
                f"heartbeat interval ({self.heartbeat_interval}s) — one "
                f"on-time heartbeat must always keep a lease alive")
        self._phys = dist.phys_rank()
        self._lock = threading.Lock()
        self._members: Tuple[int, ...] = dist.active_members()
        self._fence = dist.fence_generation()
        self._tracker = LeaseTracker(self.lease_ttl)
        now = time.monotonic()
        for r in self._members:
            if r != self._phys:
                self._tracker.track(r, now)
        self._seq = 0
        self._suspects: set = set()
        self._peer_round = False     # a peer opened a re-form round
        self._reform_needed = False
        self._fenced: Optional[str] = None   # reason, once discovered
        self._detect_ts: Optional[float] = None   # wall ts of first suspect
        self._sbar = 0                      # per-fence step-barrier counter
        self._stop = threading.Event()
        self._stall_until: Optional[float] = None   # monotonic; inf=forever
        self._hb_thread: Optional[threading.Thread] = None
        self._watch_thread: Optional[threading.Thread] = None
        reg = _metrics_registry()
        self._c_heartbeats = reg.counter(
            "dist.membership.heartbeats",
            help="lease heartbeats published by this host")
        self._c_expired = reg.counter(
            "dist.membership.expired",
            help="peer leases this host observed expiring")
        self._c_reforms = reg.counter(
            "dist.membership.reforms",
            help="fleet re-form rounds this host committed")
        self._c_fenced = reg.counter(
            "dist.membership.fenced",
            help="times this host discovered it was fenced out")
        self._g_alive = reg.gauge(
            "dist.membership.alive",
            help="peers with fresh leases (this host included)")
        self._g_world = reg.gauge(
            "dist.membership.world",
            help="active logical world size (after re-forms)")
        self._g_fence = reg.gauge(
            "dist.membership.fence",
            help="current membership fencing generation")
        self._h_reform = reg.histogram(
            "dist.membership.reform_us",
            help="wall time of one committed re-form round")
        self._g_dp = reg.gauge(
            "dist.membership.dp_size",
            help="post-re-form data-parallel world size (set when the "
                 "resilience layer re-builds the sharded step at the "
                 "new world)")
        self._h_reshard = reg.histogram(
            "dist.membership.reshard_us",
            help="wall time of the in-graph re-shard after a re-form "
                 "(sharding re-derivation + state re-placement + jit "
                 "rebuild)")
        self._g_alive.set(len(self._members))
        self._g_world.set(len(self._members))
        self._g_fence.set(self._fence)
        self._flight = _flight_recorder()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        """Publish the first lease and launch the heartbeat + watcher
        daemons (idempotent).  An atexit hook stops them on normal
        interpreter exit: a daemon mid-``kv_publish`` while the jax
        client is being destroyed at teardown is a C++ exception on a
        handlerless thread — ``terminate()``, SIGABRT."""
        if self._hb_thread is not None:
            return
        if not getattr(self, "_atexit_stop", False):
            self._atexit_stop = True
            import weakref
            ref = weakref.ref(self)

            def _stop_daemons():
                mgr = ref()
                if mgr is not None:
                    mgr.stop()

            atexit.register(_stop_daemons)
        self._stop.clear()
        self._publish_lease()
        self._hb_thread = threading.Thread(
            target=self._hb_loop, daemon=True,
            name=f"mxtpu-membership-hb-{self._phys}")
        self._watch_thread = threading.Thread(
            target=self._watch_loop, daemon=True,
            name=f"mxtpu-membership-watch-{self._phys}")
        self._hb_thread.start()
        self._watch_thread.start()

    def stop(self) -> None:
        self._stop.set()
        for t in (self._hb_thread, self._watch_thread):
            if t is not None:
                t.join(timeout=2 * self.heartbeat_interval + 1.0)
        self._hb_thread = None
        self._watch_thread = None

    # -- introspection ------------------------------------------------------
    @property
    def phys_rank(self) -> int:
        return self._phys

    @property
    def members(self) -> Tuple[int, ...]:
        with self._lock:
            return self._members

    @property
    def fence(self) -> int:
        with self._lock:
            return self._fence

    @property
    def reform_needed(self) -> bool:
        with self._lock:
            return self._reform_needed

    @property
    def suspects(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(sorted(self._suspects))

    @property
    def fenced(self) -> bool:
        with self._lock:
            return self._fenced is not None

    def raise_if_fenced(self) -> None:
        with self._lock:
            reason = self._fenced
        if reason is not None:
            raise HostFenced(reason)

    def record_reshard(self, dp_size: int, duration_us: float) -> None:
        """Record the in-graph re-shard that followed a committed
        re-form: the resilience layer rebuilds the sharded step at the
        new world size and reports the post-re-form dp size + re-shard
        wall time here, so elastic re-form timelines (metrics AND the
        flight membership ring) show the re-shard step between restore
        and resume."""
        self._g_dp.set(int(dp_size))
        self._h_reshard.observe(float(duration_us))
        self._flight.record_membership(
            event="reshard", ts=round(time.time(), 3),
            dp_size=int(dp_size), reshard_us=round(float(duration_us), 1))

    def _set_fenced(self, reason: str) -> None:
        with self._lock:
            if self._fenced is not None:
                return
            self._fenced = reason
        self._c_fenced.inc()
        self._flight.record_membership(
            event="fenced", ts=round(time.time(), 3), reason=reason)
        # a fenced host's clean jax teardown would run the full-world
        # shutdown barrier and abort the process — detach dirty instead
        _install_dirty_exit()

    # -- fault hook (heartbeat_stall) ---------------------------------------
    def stall_heartbeats(self, seconds: Optional[float] = None) -> None:
        """Freeze the lease publisher (the ``heartbeat_stall`` fault
        site): the process keeps stepping but its lease stops advancing,
        so peers reap it — the false-death/split-brain case the fencing
        generation resolves.  ``seconds=None`` stalls forever."""
        with self._lock:
            self._stall_until = float("inf") if seconds is None \
                else time.monotonic() + float(seconds)

    # -- heartbeat publisher ------------------------------------------------
    def _publish_lease(self) -> None:
        with self._lock:
            self._seq += 1
            payload = {"seq": self._seq, "fence": self._fence}
        dist.kv_publish(LEASE_PREFIX, json.dumps(payload).encode("utf-8"))
        self._c_heartbeats.inc()

    def _hb_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_interval):
            with self._lock:
                stall = self._stall_until
                if stall is not None and time.monotonic() >= stall:
                    stall = self._stall_until = None
            if stall is not None:
                continue   # fault-injected publisher freeze
            try:
                self._publish_lease()
            except Exception:   # noqa: BLE001 — a failed publish is one
                # missed heartbeat; the next interval retries and the
                # lease only dies after a full TTL of them
                continue

    # -- reaper / watcher ---------------------------------------------------
    def scan(self) -> List[int]:
        """One reaper pass: read every peer's lease, age them on this
        host's monotonic clock, flag expiries, notice peer-initiated
        re-form rounds, and check the epoch record for this host's own
        fencing.  Returns the currently-suspected dead ranks.  Called
        from the watcher daemon every heartbeat interval and forced
        synchronously by the resilience layer when a bounded collective
        times out."""
        now = time.monotonic()
        with self._lock:
            members, fence = self._members, self._fence
        try:
            leases = dist.kv_collect(LEASE_PREFIX)
        except Exception as exc:   # noqa: BLE001 — the store is gone:
            # coordinator death is fate-sharing, surface as FleetLost
            # from reform(); here just report nothing new
            leases = {}
            if not dist.is_initialized():
                raise FleetLost(
                    "membership scan: the process group is gone") from exc
        advanced = set()
        for r, blob in leases.items():
            if r == self._phys or r not in members:
                continue
            try:
                payload = json.loads(blob.decode("utf-8"))
                seq = int(payload["seq"])
            except (ValueError, KeyError, UnicodeDecodeError):
                continue
            if self._tracker.observe(r, seq, now):
                advanced.add(r)
        peers = [r for r in members if r != self._phys]
        dead = self._tracker.expired(now, peers)
        self._check_epoch(members, fence)
        self._check_peer_reform(fence)
        with self._lock:
            # a suspect whose lease ADVANCES again un-suspects: a
            # transient stall shorter than everyone's reform trigger
            # self-heals instead of leaving this host's view diverged
            # from peers that never noticed (two hosts with different
            # monotone suspect sets could otherwise elect two leaders)
            healed = (self._suspects & advanced) - set(dead)
            if healed:
                self._suspects -= healed
                if not self._suspects and not self._peer_round:
                    self._reform_needed = False
                    self._detect_ts = None
            new = set(dead) - self._suspects
            if new:
                self._suspects |= new
                self._reform_needed = True
                if self._detect_ts is None:
                    self._detect_ts = time.time()
            alive = len(members) - len(self._suspects)
        if new:
            self._c_expired.inc(len(new))
            self._flight.record_membership(
                event="suspect", ts=round(time.time(), 3),
                dead=sorted(new), members=list(members), fence=fence)
        self._g_alive.set(alive)
        return sorted(dead)

    def _check_epoch(self, members, fence) -> None:
        """Fence discovery: a committed epoch record with a NEWER fence
        that excludes this host means the fleet re-formed without it."""
        record = _epoch_record()
        if record is None:
            return
        new_fence = int(record.get("fence", 0))
        new_members = [int(m) for m in record.get("members", [])]
        if new_fence <= fence:
            return
        if self._phys not in new_members:
            self._set_fenced(
                f"host (process id {self._phys}) was fenced out at "
                f"generation {new_fence}: the surviving fleet "
                f"{new_members} re-formed without it (its lease expired "
                f"— dead to them, even if this process is still "
                f"running); exit and restart, do not rejoin")

    def _check_peer_reform(self, fence) -> None:
        """A peer that opened a re-form round for the next fence has
        already posted its view — join promptly instead of waiting for
        this host's own reaper to age the dead lease out."""
        try:
            views = _dir_by_rank(f"{MEMBER_PREFIX}/reform/"
                                 f"{fence + 1}/view")
        except Exception:   # noqa: BLE001 — transient store hiccup:
            return          # the next scan retries
        if views:
            with self._lock:
                self._peer_round = True
                self._reform_needed = True
                if self._detect_ts is None:
                    self._detect_ts = time.time()

    def _watch_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_interval):
            try:
                self.scan()
            except FleetLost:
                return   # nothing left to watch
            except Exception:   # noqa: BLE001 — one failed scan must
                continue        # not kill liveness detection

    # -- the per-step lockstep sync -----------------------------------------
    def step_barrier(self, timeout: Optional[float] = None) -> None:
        """Bounded barrier over the ACTIVE member set at a step
        boundary.  This is the blocking path a dead host breaks *fast*:
        the default timeout is ~2 lease TTLs (long enough that by the
        time it fires the dead host's lease has provably expired, short
        enough that survivors never sit out the full
        ``MXTPU_DIST_TIMEOUT``), and an absent peer raises the typed
        ``DeadlineExceeded`` the resilience layer converts into a
        forced scan + re-form."""
        if timeout is None:
            timeout = max(2.0 * self.lease_ttl, 4 * self.heartbeat_interval)
        with self._lock:
            fence, members = self._fence, self._members
            n = self._sbar
            self._sbar += 1
        timeout_ms = max(100, int(timeout * 1000))
        dist._deadline_wait(
            f"membership step_barrier {n} (fence {fence}) over ranks "
            f"{list(members)}", timeout,
            _coordination.client().wait_at_barrier,
            f"mxtpu_step_{fence}_{n}", timeout_ms, list(members))

    # -- the re-form protocol -----------------------------------------------
    def reform(self) -> ReformResult:
        """Run one re-form consensus round over the coordination-service
        KV store and install the surviving group.  EVERY survivor must
        call this (it is fleet-synchronized like a collective — the
        collective-safety lint rule checks reachability); the dead
        host(s) obviously don't, which is why no phase below uses a
        device collective or an all-ranks barrier.

        Round shape (all keys under ``mxtpu/member/reform/<fence+1>``):

        1. **view** — each survivor posts the member set it believes
           alive (own reaper verdict), then waits for a view from every
           rank in its own view, dropping ranks whose lease expires
           while waiting (cascaded death during the round).
        2. **plan** — the leader (lowest surviving rank) intersects the
           posted views (never includes a host any survivor can't see)
           and posts the member list + bumped fence.
        3. **ack/commit** — survivors in the plan ack; once every
           planned member acked, the leader writes the epoch record
           (the durable fence bump a stalled host discovers later) and
           the commit mark; everyone installs the narrowed group via
           ``dist.set_active_members`` and the leader purges the dead
           ranks' KV generations.
        4. **rejoin barrier** — over the NEW member set, so no survivor
           races ahead into a collective before its peers installed.

        Raises :class:`HostFenced` when the plan excludes this host,
        :class:`FleetLost` when the round cannot complete inside
        ``reform_timeout`` or the store is gone.
        """
        self.raise_if_fenced()
        t0 = time.monotonic()
        with self._lock:
            detect_ts = self._detect_ts
        timeline: List[Tuple[str, float]] = []
        if detect_ts is not None:
            timeline.append(("detect", round(detect_ts, 3)))
        timeline.append(("reform_start", round(time.time(), 3)))
        deadline = Deadline(self.reform_timeout)
        me = self._phys
        with self._lock:
            old_members, fence = self._members, self._fence
        fence_next = fence + 1
        base = f"{MEMBER_PREFIX}/reform/{fence_next}"
        # causal tracing: every survivor opens a (never-sampled-away)
        # re-form span and ships its traceparent on a SIDE key next to
        # its posted view (the consensus payloads stay byte-identical
        # to the pre-tracing protocol); once the views are in, everyone
        # re-parents onto the lowest-rank poster's context — a 2-proc
        # re-form stitches into ONE trace through the KV tier, no
        # matter who opened it
        trc = _tracing.tracer()
        tspan = None if not trc.enabled else trc.begin(
            "membership.reform",
            trace_id=_tracing.gen_trace_id(),
            args={"fence": fence_next, "rank": me})
        try:
            views, view_tps = self._exchange_views(base, deadline,
                                                   tspan)
            if tspan is not None and view_tps:
                low = min(view_tps)
                if low < me:
                    tspan.adopt(
                        _tracing.parse_traceparent(view_tps[low]))
            plan = self._plan_round(base, views, fence_next, deadline)
            members = tuple(sorted(int(m) for m in plan["members"]))
            timeline.append(("plan", round(time.time(), 3)))
            if me not in members:
                self._set_fenced(
                    f"host (process id {me}) was excluded by the "
                    f"re-form plan at generation {fence_next} (members "
                    f"{list(members)}): its lease expired from the "
                    f"survivors' view — exit and restart, do not rejoin")
                self.raise_if_fenced()
            self._commit_round(base, members, fence_next, deadline)
        except DeadlineExceeded as exc:
            if tspan is not None:
                tspan.annotate(error="DeadlineExceeded")
                tspan.finish()
            raise FleetLost(
                f"fleet re-form at generation {fence_next} did not "
                f"complete within {self.reform_timeout:.0f}s "
                f"(MXTPU_ELASTIC_REFORM_TIMEOUT): {exc}") from exc
        except Exception:
            if tspan is not None:
                tspan.annotate(error="reform-failed")
                tspan.finish()
            raise
        dead = tuple(sorted(set(old_members) - set(members)))
        # install: the narrowed group is live from here on this host
        dist.set_active_members(members, fence_next)
        with self._lock:
            self._members = members
            self._fence = fence_next
            self._suspects.clear()
            self._peer_round = False
            self._reform_needed = False
            self._detect_ts = None
            self._sbar = 0
        for r in dead:
            self._tracker.forget(r)
        if me == min(members):
            self._purge_dead(dead, fence)
        # rejoin barrier OVER THE NEW SET: every survivor has installed
        # before anyone's next collective
        timeout = max(1.0, deadline.remaining())
        try:
            dist._deadline_wait(
                f"re-form rejoin barrier (fence {fence_next})", timeout,
                _coordination.client().wait_at_barrier,
                f"mxtpu_reform_{fence_next}",
                max(1000, int(timeout * 1000)), list(members))
        except DeadlineExceeded as exc:
            if tspan is not None:
                tspan.annotate(error="rejoin-barrier-timeout")
                tspan.finish()
            raise FleetLost(
                f"a survivor never reached the rejoin barrier at "
                f"generation {fence_next}: {exc}") from exc
        timeline.append(("reformed", round(time.time(), 3)))
        if tspan is not None:
            tspan.annotate(members=",".join(str(m) for m in members),
                           dead=",".join(str(d) for d in dead))
            tspan.finish()
        # the original world's shutdown barrier can never complete again
        # — every survivor must detach dirty at exit (see _hard_exit)
        _install_dirty_exit()
        self._c_reforms.inc()
        self._g_world.set(len(members))
        self._g_fence.set(fence_next)
        self._g_alive.set(len(members))
        self._h_reform.observe((time.monotonic() - t0) * 1e6)
        self._flight.record_membership(
            event="reform", ts=round(time.time(), 3), fence=fence_next,
            members=list(members), dead=list(dead),
            new_rank=members.index(me), timeline=list(timeline))
        return ReformResult(
            fence=fence_next, old_members=old_members, members=members,
            dead=dead, new_rank=members.index(me),
            new_world=len(members), timeline=tuple(timeline))

    # -- round phases -------------------------------------------------------
    def _exchange_views(self, base: str, deadline: Deadline,
                        tspan=None):
        """Phase 1: post this host's view, gather every view it is
        waiting on, dropping ranks that die mid-round.

        The view payload stays the bare member list every fleet
        version parses; the causal-tracing traceparent rides a SIDE
        key (``{base}/viewtp/{rank}``) on the same KV tier, so tracing
        can never perturb the consensus and a tp-less (older or
        tracing-off) host simply stitches nothing.  Returns
        ``(views, view_tps)``."""
        me = self._phys
        self.scan()   # freshest possible verdict before voting
        with self._lock:
            view = sorted((set(self._members) - self._suspects) | {me})
        if tspan is not None:
            try:
                _kv_set(f"{base}/viewtp/{me}", tspan.traceparent)
            except Exception:   # noqa: BLE001 — tracing is
                pass            # best-effort; the round decides
        _kv_set(f"{base}/view/{me}", json.dumps(view))
        views: Dict[int, List[int]] = {}
        view_tps: Dict[int, str] = {}
        while True:
            deadline.check("re-form view exchange")
            try:
                posted = _dir_by_rank(f"{base}/view")
            except Exception as exc:   # noqa: BLE001 — store gone
                raise FleetLost(
                    "re-form view exchange: the coordination-service KV "
                    f"store is unreachable ({exc}) — coordinator loss "
                    "is fate-sharing") from exc
            for r, raw in posted.items():
                try:
                    views[r] = [int(x) for x in json.loads(raw)]
                except ValueError:
                    continue
            if all(r in views for r in view):
                if tspan is not None:
                    try:
                        view_tps = _dir_by_rank(f"{base}/viewtp")
                    except Exception:   # noqa: BLE001 — tracing is
                        view_tps = {}   # best-effort
                return ({r: v for r, v in views.items() if r in view},
                        view_tps)
            # a rank in our view may die while we wait: re-scan, shrink
            # the view, re-post so peers stop waiting on our old vote
            self.scan()
            with self._lock:
                shrunk = sorted(
                    (set(view) - self._suspects) | {me})
            if shrunk != view:
                view = shrunk
                _kv_set(f"{base}/view/{me}", json.dumps(view))
            time.sleep(self._POLL_S)

    def _plan_round(self, base: str, views: Dict[int, List[int]],
                    fence_next: int, deadline: Deadline) -> dict:
        """Phase 2: the leader intersects the views and posts the plan;
        everyone (leader included) reads it back from the store — one
        source of truth."""
        me = self._phys
        leader = min(views)
        if me == leader:
            agreed = set(views[leader])
            for v in views.values():
                agreed &= set(v)
            if me not in agreed:
                # every peer's view excludes this host: IT is the
                # false-dead one (a stalled publisher that joined a
                # peer-opened round and, having reaped nobody, elected
                # itself leader).  Authoring a plan here would re-admit
                # a host the fleet already reaped — the exact
                # split-brain fencing exists to prevent.  Fence, never
                # write the plan; the true survivors' leader (the
                # lowest rank every view agrees on) authors it, so the
                # committed plan content is the same no matter which
                # participant computes it.
                self._set_fenced(
                    f"host (process id {me}) is excluded from every "
                    f"peer's re-form view at generation {fence_next}: "
                    f"its lease expired from the survivors' side (a "
                    f"stalled heartbeat publisher reads as death) — "
                    f"exit and restart, do not rejoin")
                self.raise_if_fenced()
            with self._lock:
                old = self._members
            plan = {"fence": fence_next,
                    "members": sorted(agreed),
                    "dead": sorted(set(old) - agreed)}
            _kv_set(f"{base}/plan", json.dumps(plan))
        blob = _kv_await(f"{base}/plan", deadline, "re-form plan")
        return json.loads(blob)

    def _commit_round(self, base: str, members: Tuple[int, ...],
                      fence_next: int, deadline: Deadline) -> None:
        """Phase 3: ack, then (leader) epoch record + commit mark; wait
        for the commit."""
        me = self._phys
        _kv_set(f"{base}/ack/{me}", "1")
        if me == min(members):
            while True:
                deadline.check("re-form ack collection")
                try:
                    acked = set(_dir_by_rank(f"{base}/ack"))
                except Exception:   # noqa: BLE001 — transient read
                    acked = set()
                if all(r in acked for r in members):
                    break
                time.sleep(self._POLL_S)
            _kv_set(EPOCH_KEY, json.dumps(
                {"fence": fence_next, "members": list(members)}))
            _kv_set(f"{base}/commit", "1")
        _kv_await(f"{base}/commit", deadline, "re-form commit")

    def _purge_dead(self, dead: Tuple[int, ...], old_fence: int) -> None:
        """Leader-only, best-effort: delete the dead ranks' lease and
        published-state generations plus the PREVIOUS fence's allgather
        namespace (keys only the old full group could have written), so
        no later collect serves a dead host's frozen payload."""
        for r in dead:
            for prefix in PURGE_PREFIXES:
                try:
                    dist.kv_purge_rank(prefix, r)
                except Exception:   # noqa: BLE001 — purge best-effort
                    continue
            try:
                dist.kv_purge_rank(f"mxtpu/agb/{old_fence}", r)
            except Exception:   # noqa: BLE001 — same
                continue


# -- dirty detach ------------------------------------------------------------
#
# Once the fleet has re-formed (or this host is fenced), the ORIGINAL
# world is permanently degraded: the jax coordination client's normal
# teardown runs a Shutdown barrier over EVERY launcher task, the dead
# one included — the service then marks the barrier failed, propagates
# a fatal error to all remaining tasks, and jax's error-polling thread
# ABORTS each of their processes (SIGABRT) in response.  A survivor
# that trained through a host loss flawlessly would die at exit, and
# its abort would take the other survivors with it.  The only safe
# teardown is to never run that C++ shutdown: flush what matters
# (stdio, in-flight async checkpoint writes), then ``os._exit`` with
# the interpreter's intended status.  Installed automatically by every
# committed re-form and by fence discovery; ``sys.exit`` and unhandled
# exceptions keep their exit codes.

_dirty_exit_lock = threading.Lock()
_dirty_exit_installed = False
_dirty_exit_code = {"code": 0}   # recorded by the sys.exit patch


def _hard_exit(code: int) -> None:
    try:
        # the os._exit below skips threading._register_atexit hooks, so
        # run the resilience layer's checkpoint flush ourselves — a
        # survivor's last async write must still commit
        from .resilience import _exit_flush_trainers
        for tr in list(_exit_flush_trainers or ()):
            tr.wait_checkpoint()
    except Exception:   # noqa: BLE001 — an uncommitted write is
        pass            # skipped by resume's committed-only filter
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except Exception:   # noqa: BLE001 — exiting regardless
        pass
    try:
        if _coordination.hosts_service():
            # this process HOSTS the coordination service: its death
            # severs every peer's fabric mid-RPC, and jax's
            # error-polling thread SIGABRTs a peer whose poll hits the
            # closed socket.  Linger so peers still wrapping up — or a
            # stalled host still discovering its fence — finish with
            # their own clean exit codes first.
            time.sleep(max(0.0, float(get_env(
                "MXTPU_ELASTIC_COORD_LINGER"))))
    except Exception:   # noqa: BLE001 — exiting regardless
        pass
    os._exit(code)


def _install_dirty_exit() -> None:
    global _dirty_exit_installed
    with _dirty_exit_lock:
        if _dirty_exit_installed:
            return
        _dirty_exit_installed = True

    def exit_now(code=0):
        # record the status for the atexit layer, then raise SystemExit
        # like the real sys.exit: the caller's finally blocks and
        # context managers UNWIND normally — only the very last step of
        # interpreter shutdown is replaced by the dirty os._exit
        if code is None:
            _dirty_exit_code["code"] = 0
        elif isinstance(code, int):
            _dirty_exit_code["code"] = code
        else:
            print(code, file=sys.stderr)
            _dirty_exit_code["code"] = 1
        raise SystemExit(code)

    sys.exit = exit_now
    prev_hook = sys.excepthook

    def hook(etype, value, tb):
        prev_hook(etype, value, tb)   # flight-recorder dump chain runs
        _hard_exit(1)

    sys.excepthook = hook
    # normal end-of-script (and the SystemExit path above): atexit
    # hooks run AFTER the threading._register_atexit checkpoint flush,
    # so state is safe by the time this fires (and os._exit skips jax's
    # own atexit hooks, which is the point).  Known caveat: a top-level
    # `raise SystemExit(n)` (instead of the idiomatic sys.exit(n),
    # which is patched above) reaches this hook with no way to read the
    # pending status — it exits 0.
    atexit.register(lambda: _hard_exit(_dirty_exit_code["code"]))


# -- module helpers ----------------------------------------------------------

_client = _coordination.client


def _kv_set(key: str, value: str) -> None:
    _client().key_value_set(key, value, allow_overwrite=True)


def _kv_await(key: str, deadline: Deadline, what: str) -> str:
    """Poll one key with short bounded reads until it appears or the
    round deadline expires (the round-level ``DeadlineExceeded`` is the
    caller's FleetLost signal)."""
    while True:
        deadline.check(what)
        wait_ms = max(50, min(500, int(deadline.remaining() * 1000)))
        try:
            return _client().blocking_key_value_get(key, wait_ms)
        except Exception as exc:   # noqa: BLE001 — DEADLINE_EXCEEDED on
            # this short poll is just 'not yet'; anything else is a
            # store failure worth surfacing
            if "DEADLINE_EXCEEDED" in str(exc):
                continue
            raise FleetLost(
                f"{what}: the coordination-service KV store is "
                f"unreachable ({exc})") from exc


def _dir_by_rank(prefix: str) -> Dict[int, str]:
    """Keys shaped ``{prefix}/{rank}`` → ``{rank: raw_value}`` (the
    re-form round's view/ack namespaces — written with plain overwrite
    sets, unlike the gen-stamped ``kv_publish`` lease shape)."""
    out: Dict[int, str] = {}
    for key, value in _client().key_value_dir_get(prefix):
        try:
            out[int(key.rsplit("/", 1)[1])] = value
        except (ValueError, IndexError):
            continue
    return out


def _epoch_record() -> Optional[dict]:
    """The committed membership epoch record, or None before the first
    re-form.  Non-blocking (a one-entry dir read, not a blocking get)."""
    try:
        for key, value in _client().key_value_dir_get(EPOCH_DIR):
            if key == EPOCH_KEY:
                return json.loads(value)
    except Exception:   # noqa: BLE001 — missing dir / transient store
        return None
    return None
