"""Profiler (reference: src/profiler/ + python/mxnet/profiler.py,
SURVEY.md §5.1).

Two levels, mirroring the reference:
- **Op events** from the engine's dispatch listener → chrome://tracing JSON
  (``dump()``), the analog of the reference's OprBlock begin/end events.
  Dispatch wall-time is recorded; because XLA dispatch is async, per-op
  *device* time lives in the device trace below (the reference had the
  same split: engine events vs CUDA kernels).
- **The device trace** via ``jax.profiler`` when ``profile_all=True``:
  written to ``trace_dir`` if configured, else to ``<filename>_xla/`` next
  to the chrome trace — the analog of nvprof/NVTX.  After ``stop``,
  ``dumps()`` reduces its ``.xplane.pb`` to the three tables an operator
  wants here: device self time by the program's own scopes (the Gluon
  blocks, ``loss``, ``optimizer``, the kernels: ``jax.named_scope`` names
  that every compiled program carries), the program's host spans
  (``trace.span`` = ``mx.<name>`` annotations) by name, and the device's
  idle gaps by the span that covers them.  Without a device trace
  ``dumps()`` is the table of engine-op dispatch times it always was.
- **The compiled step's own table**, ``step_scopes()``: which scope every
  device instruction of the train step belongs to, from the executable
  that ``ShardedTrainer`` built and handed over, read when asked.  It gives
  a scope to what a trace alone leaves without one (``dumps()`` marks that
  time as inferred) and is what the benchmark's per-layer device times
  read.
"""
from __future__ import annotations

import glob
import io
import json
import os
import re
import threading
import time
import weakref
from typing import Dict, List, Optional

from .base import MXNetError
from .engine import engine

__all__ = ["set_config", "set_state", "state", "pause", "resume", "dump",
           "dumps", "step_scopes", "Profiler"]


class Profiler:
    _inst: Optional["Profiler"] = None

    def __init__(self):
        self.filename = "profile_output.json"
        self.profile_all = False
        self.aggregate_stats = True
        self.trace_dir: Optional[str] = None
        self._running = False
        self._paused = False
        self._events: List[dict] = []
        self._agg: Dict[str, List[float]] = {}
        self._lock = threading.Lock()
        self._listener_installed = False
        self._tracing_device = False
        self._xplane: Optional[str] = None   # the last device trace written
        # the train step a trainer compiled last: its executable while its
        # trainer lives, its text after, the table once asked (step_scopes)
        self._step_compiled = None
        self._step_text: Optional[str] = None
        self._step_table: Optional[Dict[str, tuple]] = None
        self._t0 = time.perf_counter()
        # ONE timeline for the whole fleet: pid = this process's host
        # index (resolved lazily — profiling may start before the
        # process group), tid = a small per-thread lane so supervisor
        # steps, loader workers, and engine flushes land on separate
        # rows of the same chrome trace
        self._pid: Optional[int] = None
        self._tids: Dict[int, int] = {}      # thread ident -> lane
        self._tnames: Dict[int, str] = {}    # lane -> thread name

    def _host_pid(self) -> int:
        # cached so the per-event path never probes; start() clears the
        # cache, so each profiling session re-resolves — a session begun
        # AFTER init_process_group gets the real host index even if an
        # earlier pre-init session cached the single-process fallback
        if self._pid is None:
            try:
                from .parallel.dist import is_initialized
                if is_initialized():
                    import jax
                    self._pid = jax.process_index()
                else:
                    self._pid = 0
            except Exception:   # noqa: BLE001 — a broken dist probe must
                self._pid = 0   # not break profiling
        return self._pid

    def _lane_locked(self) -> int:
        """Small stable per-thread tid; callers hold self._lock (the
        ``_locked`` suffix is the lint-checked convention for that)."""
        ident = threading.get_ident()
        lane = self._tids.get(ident)
        if lane is None:
            lane = len(self._tids)
            self._tids[ident] = lane
            self._tnames[lane] = threading.current_thread().name
        return lane

    @classmethod
    def get(cls) -> "Profiler":
        if cls._inst is None:
            cls._inst = Profiler()
        return cls._inst

    # -- engine listener ---------------------------------------------------
    def _on_op(self, op_name: str, outputs, dispatch_us: float = 0.0) -> None:
        if not self._running or self._paused:
            return
        now = (time.perf_counter() - self._t0) * 1e6   # µs
        dur = max(dispatch_us, 0.1)                    # measured, not gap
        pid = self._host_pid()
        with self._lock:
            self._events.append({
                "name": op_name, "ph": "X", "pid": pid,
                "tid": self._lane_locked(), "ts": now - dur, "dur": dur,
                "cat": "operator"})
            self._agg.setdefault(op_name, []).append(dur)

    # -- span listener (trace.span -> unified timeline) --------------------
    def _on_span(self, name: str, t_end: float, dur_us: float,
                 args: Optional[dict] = None) -> None:
        """``trace.span`` exits land here as PROPER duration events:
        supervisor steps, engine flushes, and loader batches appear on
        the same timeline as per-op events, with pid = host index and
        tid = thread lane (nested spans render stacked, chrome-trace
        semantics).  Span ``args`` (step number, batch id, ...) become
        the chrome-trace event's ``args``, so the timeline answers
        "which step was this?" on hover."""
        if not self._running or self._paused:
            return
        ts_end = (t_end - self._t0) * 1e6              # µs
        dur = max(dur_us, 0.1)
        pid = self._host_pid()
        ev = {"name": name, "ph": "X", "pid": pid, "ts": ts_end - dur,
              "dur": dur, "cat": "span"}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            ev["tid"] = self._lane_locked()
            self._events.append(ev)
            self._agg.setdefault("mx." + name, []).append(dur)

    def start(self) -> None:
        self._pid = None               # re-resolve host index per session
        self._host_pid()
        if not self._listener_installed:
            engine().add_listener(self._on_op)
            from .observability.trace import add_span_listener
            add_span_listener(self._on_span)
            self._listener_installed = True
        self._running = True
        if self.profile_all and not self.trace_dir:
            # profile_all without an explicit trace_dir: put the XLA trace
            # next to the chrome-trace file (documented behavior)
            self.trace_dir = self.filename + "_xla"
        if self.profile_all and self.trace_dir:
            import jax
            self._xplane = None
            jax.profiler.start_trace(self.trace_dir)
            self._tracing_device = True

    def stop(self) -> None:
        if self._tracing_device:
            import jax
            self._tracing_device = False
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass
            else:
                self._xplane = find_xplane(self.trace_dir)
        self._running = False
        # drop the engine tap: an installed listener makes every invoke
        # pay dispatch timing AND suspends bulked dispatch — a stopped
        # profiler must cost nothing (start() re-installs)
        if self._listener_installed:
            engine().remove_listener(self._on_op)
            from .observability.trace import remove_span_listener
            remove_span_listener(self._on_span)
            self._listener_installed = False

    # -- output ------------------------------------------------------------
    #: tracing events render on their own tid lanes, offset past the
    #: profiler's per-thread lanes so the two namespaces never collide
    _TRACE_TID_BASE = 64

    def dump(self, finished: bool = True) -> None:
        pid = self._host_pid()
        with self._lock:
            # chrome-trace metadata names the lanes: the process row is
            # the host, each tid row the thread that emitted its events
            meta = [{"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": f"host {pid}"}}]
            for lane, tname in sorted(self._tnames.items()):
                meta.append({"name": "thread_name", "ph": "M",
                             "pid": pid, "tid": lane,
                             "args": {"name": tname}})
            events = list(self._events)
        # causal-tracing merge: the tracer's completed-span ring joins
        # the op/span timeline as duration events PLUS flow arrows
        # (parent -> child, batch -> member requests) on the same
        # perf_counter clock — the profiler's view of "what caused
        # what", not just "what ran when"
        try:
            from .observability import tracing as _tracing
            trc = _tracing.tracer()
            tev = trc.chrome_events(base_pc=self._t0,
                                    tid_offset=self._TRACE_TID_BASE)
            if tev:
                for lane, tname in sorted(trc.lane_names().items()):
                    meta.append({"name": "thread_name", "ph": "M",
                                 "pid": pid,
                                 "tid": self._TRACE_TID_BASE + lane,
                                 "args": {"name": f"trace:{tname}"}})
                events += tev
        except Exception:   # noqa: BLE001 — a broken tracer must not
            pass            # break the profile dump
        payload = {"traceEvents": meta + events,
                   "displayTimeUnit": "ms"}
        with open(self.filename, "w") as f:
            json.dump(payload, f)

    def dumps(self, reset: bool = False, depth: int = 4) -> str:
        """The aggregate table.  With a device trace from the last
        ``run``..``stop`` (``profile_all=True``): device self time by
        scope down to ``depth`` names, forward and backward apart; the
        ``mx.*`` host spans; idle gaps by owner (:func:`reduce_trace`).
        Without one: the engine ops' host dispatch times, which say
        nothing about the device and are not shown beside a trace."""
        if self._xplane is not None:
            text = format_tables(reduce_trace(load_xplane(self._xplane),
                                              depth, step_scopes()))
            if reset:
                self._xplane = None
            return text
        with self._lock:
            rows = []
            for name, durs in sorted(self._agg.items()):
                total = sum(durs)
                rows.append((name, len(durs), total, total / len(durs),
                             min(durs), max(durs)))
            if reset:
                self._agg.clear()
        head = (f"{'Name':<32}{'Calls':>8}{'Total(us)':>14}"
                f"{'Avg(us)':>12}{'Min(us)':>12}{'Max(us)':>12}\n")
        lines = ["host dispatch time (no device trace was taken)\n", head,
                 "-" * len(head) + "\n"]
        for name, calls, total, avg, mn, mx in rows:
            lines.append(f"{name:<32}{calls:>8}{total:>14.1f}"
                         f"{avg:>12.1f}{mn:>12.1f}{mx:>12.1f}\n")
        # the engine's bulk/dispatch counters ride along.  While the
        # profiler is installed, bulking suspends (listeners need real
        # per-op outputs), so the rows above are true per-op dispatch
        # costs; this footer still reports what bulking did around the
        # profiled window (segments, mean length, fused-exec cache rate)
        s = engine().stats()
        lines.append("\nengine dispatch/bulking stats:\n")
        for k in ("ops_dispatched", "ops_bulked", "segments_flushed",
                  "mean_segment_length", "segment_cache_hits",
                  "segment_cache_misses", "flush_us_p50", "flush_us_p99"):
            lines.append(f"  {k:<24}{s[k]}\n")
        return "".join(lines)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._agg.clear()
        self._xplane = None


def set_config(**kwargs) -> None:
    """reference: mx.profiler.set_config(profile_all=..., filename=...)"""
    p = Profiler.get()
    if "filename" in kwargs:
        p.filename = kwargs.pop("filename")
    if "profile_all" in kwargs:
        p.profile_all = bool(kwargs.pop("profile_all"))
    if "aggregate_stats" in kwargs:
        p.aggregate_stats = bool(kwargs.pop("aggregate_stats"))
    if "trace_dir" in kwargs:
        p.trace_dir = kwargs.pop("trace_dir")
    # reference accepts (and we ignore) profile_symbolic/imperative/memory/
    # api — one dispatch funnel means one event stream here
    kwargs.pop("profile_symbolic", None)
    kwargs.pop("profile_imperative", None)
    kwargs.pop("profile_memory", None)
    kwargs.pop("profile_api", None)
    if kwargs:
        raise MXNetError(f"unknown profiler config keys {sorted(kwargs)}")


def set_state(state_: str = "stop") -> None:
    """'run' or 'stop' (reference: mx.profiler.set_state)."""
    p = Profiler.get()
    if state_ == "run":
        p.start()
    elif state_ == "stop":
        p.stop()
    else:
        raise MXNetError("state must be 'run' or 'stop'")


def state() -> str:
    return "run" if Profiler.get()._running else "stop"


def pause() -> None:
    Profiler.get()._paused = True


def resume() -> None:
    Profiler.get()._paused = False


def dump(finished: bool = True) -> None:
    Profiler.get().dump(finished)


def dumps(reset: bool = False, depth: int = 4) -> str:
    return Profiler.get().dumps(reset, depth)


# -- a device trace (.xplane.pb), reduced ------------------------------------
#
# The device plane ``/device:TPU:<n>`` holds, in its line ``XLA Ops``, one
# event per operation the chip ran.  ``jax.profiler.ProfileData`` gives each
# event's HLO text, start and duration; the scope (the HLO ``op_name``) is a
# stat of the event's *metadata* (``tf_op``), which ProfileData does not
# hand out, so a few lines of protobuf wire format read that one table.
# The CPU backend's events carry no scope at all (``hlo_module`` and
# ``hlo_op`` only): the by-scope table is then empty and says so.

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "mx."
SHORT_GAP_NS = 20e3   # shorter gaps lie between two operations of one program
#: names jax puts into an op_name that are not scopes of the program
_NOT_A_SCOPE = re.compile(
    r"^((p?jit|vmap|shard_map)\(.*|while|cond|body|branch_\d+_fun|"
    r"closed_call|custom_(jvp|vjp)_call(_jaxpr)?)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1] if files else None


def _wire_fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as memoryviews."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        val = shift = 0
        while True:
            c = buf[i]
            i += 1
            val |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                return val

    while i < n:
        key = varint()
        if key & 7 == 0:
            val = varint()
        else:
            width = varint() if key & 7 == 2 else {1: 8, 5: 4}[key & 7]
            val = buf[i:i + width]
            i += width
        yield key >> 3, val


def _op_names(raw) -> Dict[str, Dict[str, str]]:
    """{plane name: {event name (the HLO text): op_name}} from the bytes of
    an XSpace: XSpace.planes=1; XPlane.name=2, .event_metadata=4 and
    .stat_metadata=5 (maps: key=1, value=2); XEventMetadata.name=2,
    .stats=5; XStat.metadata_id=1, .str_value=5, .ref_value=7;
    XStatMetadata.id=1, .name=2."""
    out = {}
    for no, plane in _wire_fields(memoryview(raw)):
        if no != 1:
            continue
        name, stat_names, metas = "", {}, []
        for f, v in _wire_fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 5:
                md = dict(_wire_fields(dict(_wire_fields(v))[2]))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
            elif f == 4:
                metas.append(dict(_wire_fields(v))[2])
        if not name.startswith(DEVICE_PREFIX):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        scopes = out[name] = {}
        for meta in metas:
            ev_name, scope = "", ""
            for f, v in _wire_fields(meta):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_wire_fields(v))
                    if st.get(1) in tf_op:
                        scope = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7), "")
            if scope:
                scopes[ev_name] = scope
    return out


def load_xplane(path: str) -> dict:
    """``{"device": {plane: [[HLO text, op_name, start ns, duration ns]]},
    "host": [[span name, thread, start ns, duration ns]]}``: every device
    plane's operations with their scope, and the program's own host
    spans."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    scopes = _op_names(raw)
    device, host = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            names = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [ev.name, names.get(ev.name, ""),
                         float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events]
        else:
            for line in plane.lines:
                host += [[ev.name, line.name, float(ev.start_ns),
                          float(ev.duration_ns)] for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX)]
    return {"device": device, "host": host}


def scope_of(op_name: str, depth: int):
    """(scope, "fwd" | "bwd") of one device operation.  The op_name is
    ``jit(step_fn)/transpose(jvp(bertmodel0))/enc/cell3/attn/jit(fn)/mul``:
    the jit's name, then the program's scopes (the first inside jax's
    ``jvp``/``transpose``, which mark the backward), then jax's own names
    down to the primitive.  The scope is the program's part, cut to
    ``depth`` names, with a block's number starred so that twelve layers
    make one row; ``""`` where the program named nothing.  Under a
    rematerialised block (``ShardedTrainer(remat=...)``) the scope reads
    ``.../layer*/remat/mla/...``, and ``.../layer*/remat/recompute/mla/...``
    for the forward that the backward runs again."""
    parts = op_name.split("/")
    way = "bwd" if any(p.startswith("transpose(") for p in parts) else "fwd"
    if parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    own = []
    for p in parts[:-1]:            # the last name is the primitive's
        while p.startswith(("transpose(", "jvp(")) and p.endswith(")"):
            p = p[p.index("(") + 1:-1]
        if p == "checkpoint":
            continue
        if p == "rematted_computation":
            p = "recompute"         # the forward, run again in the backward
        if not p or _NOT_A_SCOPE.match(p):
            break
        p = re.sub(r"\d+$", "*", p)
        if own and p == own[0]:
            # the backward of a rematerialised block says its path twice
            # (``.../layer1/remat/jvp(net0)/layer1/remat/checkpoint/...``):
            # the root's name starts it again
            own = []
        own.append(p)
    return "/".join(own[:depth]), way


# -- the compiled step's own table: instruction -> scope ---------------------
#
# A device trace names each operation by its HLO instruction (``fusion.884``)
# and carries the instruction's ``op_name`` where XLA kept one.  The compiled
# module has more: every instruction of every computation with its operands,
# so an instruction that states no scope (a cloned constant, a prefetch's
# ``copy-start`` / ``copy-done``, the grouped matmul's custom call, whose
# ``op_name`` is XLA's own ``ragged-dot-none``) can be given the scope of the
# instructions that read it.  The trainer hands over the executable it built
# (``parallel/trainer.py:_publish_step``); its text is read when somebody asks.

_WAYS = ("fwd", "recompute", "bwd")
#: ``name = shape opcode(operands), attributes`` with the shape skipped by hand
_HLO_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_REF = re.compile(r"%?([A-Za-z_][\w.\-]*)")
_HLO_FUSED = re.compile(r"\bfusion\(.*?\bcalls=%?([\w.\-]+)")


def publish_step(compiled, owner) -> None:
    """Keep ``compiled`` (a ``jax.stages.Compiled``; nothing else of the
    trainer ``owner``: not its jitted function, not an argument) as the
    step that :func:`step_scopes` answers for.  Nothing is read here.

    A loaded executable keeps its temporaries reserved on the device for
    as long as it lives (5.2 GB for the BERT cell's step, 7.6 GB for the
    SmallThinker cell's: PERF.md, PR 38), so it may not outlive its
    trainer here: when ``owner`` is collected the text is read (0.1-0.3 s
    for those steps) and the executable let go."""
    p = Profiler.get()
    p._step_compiled, p._step_text, p._step_table = compiled, None, None
    weakref.finalize(owner, _owner_gone, compiled).atexit = False


def _owner_gone(compiled) -> None:
    p = Profiler.get()
    if p._step_compiled is compiled:
        p._step_text, p._step_compiled = compiled.as_text(), None


def step_scopes() -> Optional[Dict[str, tuple]]:
    """``{instruction name: (scope, "fwd" | "recompute" | "bwd", inferred)}``
    for the train step most recently compiled by a ``ShardedTrainer``, or
    None where none was.  The first call parses the executable's text
    (``compiled.as_text()``, :func:`scopes_from_hlo`); the table is kept
    and the executable, or its text, let go."""
    p = Profiler.get()
    if p._step_compiled is not None:
        p._step_text, p._step_compiled = p._step_compiled.as_text(), None
    if p._step_text is not None:
        p._step_table, p._step_text = scopes_from_hlo(p._step_text), None
    return p._step_table


def scope_way(op_name: str, depth: int = 6):
    """:func:`scope_of` with the forward that a rematerialised block runs
    again as a way of its own: ``(scope, "fwd" | "recompute" | "bwd")``,
    ``recompute`` taken out of the path before it is cut to ``depth``."""
    scope, way = scope_of(op_name, depth + 1)
    parts = scope.split("/") if scope else []
    if "recompute" in parts:
        parts.remove("recompute")
        way = "recompute"
    return "/".join(parts[:depth]), way


def _hlo_instructions(text: str):
    """[(name, opcode, operand names, op_name)] of every instruction of a
    module's text outside its fused computations (the trace shows the
    fusion, not its inside): the entry, ``while`` bodies and conditions,
    called computations.  As the text lists them: operands before users."""
    fused = set(_HLO_FUSED.findall(text))
    rows, skip = [], False
    for line in io.StringIO(text):      # line by line: the text is tens of MB
        line = line.rstrip("\n")
        if line.endswith("{") and not line.startswith(" "):
            head = line.split(" ", 2)
            skip = (head[1] if head[0] == "ENTRY" else head[0]) \
                .lstrip("%") in fused
            continue
        m = None if skip else _HLO_NAME.match(line)
        if m is None:
            continue
        i = m.end()
        if line[i] == "(":                      # a tuple's shape
            i = _closing(line, i) + 1
        i = line.index(" ", i) + 1
        j = line.index("(", i)
        k = _closing(line, j)
        at = line.find('metadata={op_name="', k)
        op_name = ""
        if at >= 0:
            at += len('metadata={op_name="')
            op_name = line[at:line.index('"', at)]
        rows.append((m.group(1), line[i:j],
                     _HLO_REF.findall(line[j + 1:k]), op_name))
    names = {r[0] for r in rows}
    return [(n, code, [o for o in ops if o in names], op)
            for n, code, ops, op in rows]


def _closing(line: str, i: int) -> int:
    """Index of the ``)`` that closes the ``(`` at ``i``."""
    depth = 0
    for j in range(i, len(line)):
        c = line[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return j
    raise ValueError(f"unbalanced parentheses in {line[:120]!r}")


def scopes_from_hlo(text: str, depth: int = 6) -> Dict[str, tuple]:
    """The table of :func:`step_scopes` from a compiled module's text.

    An instruction whose ``op_name`` names a scope of the program's states
    it (:func:`scope_way`), and only stated scopes are evidence.  One that
    states none is given the scope of the nearest instructions that state
    one, reached through others that state none (so a ``copy-start``
    reaches through its ``copy-done``, a cloned constant through the tuple
    a ``while`` takes, a value through its ``bitcast``): their longest
    common prefix where they disagree, ``inferred`` true.  Which side is
    asked first follows from what the instruction is.  One XLA made by
    itself (no ``op_name``: a prefetch, a layout ``copy``, a zero-fill)
    serves the instructions that use it: users first, else operands.  One
    that computes what the program asked for and lost its place (an
    ``op_name`` that is XLA's own, as the grouped matmul's
    ``ragged-dot-none``, or a primitive at the step's top level) belongs
    where its inputs were made: operands first, else users, so the expert
    weights' gradient is the expert layer's and not the optimizer's that
    reads it.  The side asked first settles it: where its scopes share no
    prefix (a weight's prefetch that a block and the optimizer both read)
    the instruction stays ``("", "fwd", False)``, as do parameters, which
    say nothing about who reads them; the other side is heard only where
    its answer lies inside the first's (a grouped matmul whose operands
    share no more than ``lm/layer*/remat``, one of them being the
    checkpoint's copy of a weight, and whose user is in ``.../moe/experts``
    is in ``.../moe/experts``).  The way follows from the order a
    step runs in (forward, the forward run again, backward): no later than
    the first of the users, no sooner than the last of the operands.
    Nothing is inferred from a time or from an instruction's name."""
    rows = _hlo_instructions(text)
    table, users = {}, {}
    for name, _, operands, op_name in rows:
        table[name] = (*scope_way(op_name, depth), False)
        for o in operands:
            users.setdefault(o, []).append(name)

    def nearest(order, neighbours):
        """{name: the (scope, way)s stated nearest to it on one side}."""
        seen, nothing = {}, frozenset()
        for name, code, _, _ in order:
            found = set()
            for n in neighbours.get(name, ()):
                scope, way, _ = table[n]
                if scope:
                    found.add((scope, way))
                else:
                    found |= seen.get(n, nothing)
            seen[name] = nothing if code == "parameter" else frozenset(found)
        return seen

    above = nearest(rows, {name: operands for name, _, operands, _ in rows})
    below = nearest(reversed(rows), users)
    inferred = {}
    for name, code, _, op_name in rows:
        if table[name][0] or code == "parameter":
            continue
        asked = [(below[name], min), (above[name], max)]
        if op_name:
            asked.reverse()
        if not asked[0][0]:
            asked.reverse()
        (found, pick), (other, _) = asked
        got = _agreed(found, pick) if found else None
        if got:
            finer = _agreed(other, pick) if other else None
            if finer and finer[0].startswith(got[0] + "/"):
                got = finer[0], got[1]
            inferred[name] = (*got, True)
    table.update(inferred)
    return table


def _agreed(found, pick):
    """(longest common prefix, way) of some (scope, way)s; None where the
    scopes share no prefix."""
    scopes = [scope.split("/") for scope, _ in found]
    common = scopes[0]
    for parts in scopes[1:]:
        k = 0
        while k < min(len(common), len(parts)) and common[k] == parts[k]:
            k += 1
        common = common[:k]
    if not common:
        return None
    return "/".join(common), pick((w for _, w in found), key=_WAYS.index)


def _self_times(events):
    """[[event, self ns]]: each event's duration less that of the events
    nested in it (a ``while`` holds the operations of its body; a span
    the spans inside it).  ``events`` end in (start, duration)."""
    out, stack = [], []         # stack of [end, index into out]
    for ev in sorted(events, key=lambda e: (e[-2], -e[-1])):
        start, dur = ev[-2], ev[-1]
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([ev, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def reduce_trace(trace: dict, depth: int = 4,
                 table: Optional[Dict[str, tuple]] = None) -> dict:
    """The three tables, as numbers (seconds, a device plane's average):

    ``busy_s``; ``scopes``: {scope: {"fwd": s, "bwd": s}} of device self
    time, ``""`` for what no scope of the program's covers, and
    ``unscoped``: that rest by operation; ``spans``: {mx.<name>: [count,
    total s, self s]}; ``gaps``: {owner: s} of the idle gaps of 20 us and
    more, each given to the innermost (shortest) ``mx.*`` span that covers
    most of it.

    ``table`` (:func:`step_scopes`) gives an operation whose own
    ``op_name`` states no scope the one inferred for its instruction;
    ``inferred``: {scope: s} is the part of ``scopes`` that came so (in
    a row's sum already), at most six names deep."""
    n = max(len(trace["device"]), 1)
    spans_in = trace["host"]
    busy = 0.0
    scopes, unscoped, gaps, spans, inferred = {}, {}, {}, {}, {}
    for events in trace["device"].values():
        merged = []
        for _, _, s, d in sorted(events, key=lambda e: e[2]):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], s + d)
            else:
                merged.append([s, s + d])
        busy += sum(e - s for s, e in merged)
        for (hlo, op_name, _, _), self_ns in _self_times(events):
            scope, way = scope_of(op_name, depth)
            op = hlo.split(" = ", 1)[0].lstrip("%")
            entry = table.get(op) if table and not scope else None
            if entry and entry[0]:
                scope, way = _as_path(*entry[:2], depth)
                if entry[2]:
                    inferred[scope] = inferred.get(scope, 0.0) \
                        + self_ns / n / 1e9
            row = scopes.setdefault(scope, {"fwd": 0.0, "bwd": 0.0})
            row[way] += self_ns / n / 1e9
            if not scope:
                op = re.sub(r"\.\d+$", "", op)
                unscoped[op] = unscoped.get(op, 0.0) + self_ns / n / 1e9
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 < SHORT_GAP_NS:
                continue
            owner, best = "unowned", (0.0, 0.0)
            for name, _, s, d in spans_in:
                cover = min(s + d, s1) - max(s, e0)
                if cover > 0 and (cover, -d) > best:
                    owner, best = name, (cover, -d)
            gaps[owner] = gaps.get(owner, 0.0) + (s1 - e0) / n / 1e9
    for thread in {ev[1] for ev in spans_in}:
        for (name, _, _, dur), self_ns in _self_times(
                [ev for ev in spans_in if ev[1] == thread]):
            row = spans.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += dur / 1e9
            row[2] += self_ns / 1e9
    return {"busy_s": busy / n / 1e9, "scopes": scopes,
            "unscoped": unscoped, "inferred": inferred, "spans": spans,
            "gaps": gaps}


def _as_path(scope: str, way: str, depth: int):
    """A row of :func:`reduce_trace` for an entry of the step's table:
    ``recompute`` back in the path where :func:`scope_of` has it (behind
    the ``remat`` scope that ``ShardedTrainer(remat=...)`` opens; in front
    where there is none) and, as there, counted with the backward."""
    parts = scope.split("/")
    if way == "recompute":
        at = parts.index("remat") + 1 if "remat" in parts else 0
        parts.insert(at, "recompute")
        way = "bwd"
    return "/".join(parts[:depth]), way


def format_tables(red: dict, top: int = 12) -> str:
    busy = red["busy_s"] or float("nan")
    inferred = red.get("inferred", {})
    out = [f"device self time by scope (busy {red['busy_s']:.6f} s a "
           f"device; ~inferred: the part of a row whose operations state "
           f"no scope and were given their users' or operands')\n",
           f"{'Scope':<56}{'fwd(s)':>11}{'bwd(s)':>11}{'% busy':>8}"
           f"{'~inferred(s)':>14}\n"]
    rows = sorted(red["scopes"].items(),
                  key=lambda kv: -(kv[1]["fwd"] + kv[1]["bwd"]))
    for scope, t in rows:
        out.append(f"{scope or '(no scope of the program)':<56}"
                   f"{t['fwd']:>11.6f}{t['bwd']:>11.6f}"
                   f"{100 * (t['fwd'] + t['bwd']) / busy:>8.2f}"
                   + (f"{'~':>5}{inferred[scope]:.6f}"
                      if scope in inferred else "") + "\n")
    if not any(scope for scope, _ in rows):
        out.append("  (this trace's device events carry no op_name)\n")
    elif red["unscoped"]:
        out.append("  without a scope, by operation: " + ", ".join(
            f"{k} {v:.6f}" for k, v in sorted(
                red["unscoped"].items(), key=lambda kv: -kv[1])[:top])
            + "\n")
    out.append(f"\nhost spans\n{'Span':<40}{'Calls':>8}{'Total(s)':>12}"
               f"{'Self(s)':>12}\n")
    for name, (calls, total, self_s) in sorted(
            red["spans"].items(), key=lambda kv: -kv[1][1]):
        out.append(f"{name:<40}{calls:>8}{total:>12.6f}{self_s:>12.6f}\n")
    out.append(f"\ndevice idle gaps of 20 us and more, by the span "
               f"covering them\n{'Owner':<40}{'Idle(s)':>12}\n")
    for name, idle in sorted(red["gaps"].items(), key=lambda kv: -kv[1]):
        out.append(f"{name:<40}{idle:>12.6f}\n")
    return "".join(out)
