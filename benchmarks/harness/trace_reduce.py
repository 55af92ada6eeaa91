"""From the profiler's ``.xplane.pb`` to numbers: device busy seconds, the
time of each device operation, the time of a named kernel, and the longest
idle gaps with what the host was doing in them.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per operation the
chip ran (start and duration in nanoseconds).  Host planes hold the
``TraceAnnotation`` spans (the benchmark's are named ``bench.<name>``).
"""
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
SHORT_GAP_NS = 20e3     # shorter gaps lie between two operations of one program


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """[(plane name, [(line name, [(event name, start ns, duration ns)])])]
    from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def device_ops(planes):
    """{device plane name: [(op name, start ns, duration ns)]}."""
    out = {}
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PREFIX):
            continue
        for lname, events in lines:
            if lname == OPS_LINE and events:
                out[pname] = events
    return out


def host_spans(planes):
    """[(name, start ns, end ns)] of the benchmark's own annotations."""
    out = []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            continue
        for _, events in lines:
            for name, start, dur in events:
                if name.startswith(HOST_SPAN_PREFIX):
                    out.append((name[len(HOST_SPAN_PREFIX):], start,
                                start + dur))
    return out


def op_name(event_name):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the
    operation's own name, without its operands."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def family(name):
    """``fusion.12`` -> ``fusion``: instances of one operation together."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def self_times(events):
    """[(name, self ns)]: each event's duration less that of the events
    nested in it (a ``while`` holds the operations of its body)."""
    out, stack = [], []         # stack of [end, index into out]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def reduce(planes, window_s, top=10):
    """The reduction the result line carries.

    ``busy_s``: union of the intervals in which an operation ran, averaged
    over the device planes.  ``ops``: {operation name: self seconds} summed
    over events and averaged over planes; ``device_ops`` ranks them with the
    instances of one operation (``fusion.1``, ``fusion.2``) taken together.  ``gaps``: the idle time between operations,
    summed by the host span that covers most of each gap.  Returns None
    where no operation ran on any device."""
    per_dev = device_ops(planes)
    if not per_dev:
        return None
    spans = host_spans(planes)
    n = len(per_dev)
    busy_ns, ops, gaps = 0.0, {}, {}
    for events in per_dev.values():
        merged = _union([(s, s + d) for _, s, d in events])
        busy_ns += sum(e - s for s, e in merged)
        for name, d in self_times(events):
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + d
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            owner = _owner(spans, e0, s1) if s1 - e0 >= SHORT_GAP_NS \
                else "device:between_ops_under_20us"
            gaps[owner] = gaps.get(owner, 0.0) + (s1 - e0)
    # an operation that alone takes 2% of the busy time keeps its own
    # name; the others go together with the instances of their kind
    fams, alone = {}, {}
    for name, d in ops.items():
        if d >= 0.02 * busy_ns:
            alone[name] = d
        else:
            fams.setdefault(family(name), []).append(d)
    fams = {(k if len(v) == 1 else f"{k}.* (x{len(v)})"): sum(v)
            for k, v in fams.items()}
    fams.update(alone)

    def rank(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / n / 1e9, "window_s": window_s,
            "ops": {k: v / n / 1e9 for k, v in ops.items()},
            "device_ops": rank(fams), "idle_gaps": rank(gaps)}


def _owner(spans, start, end):
    best, cover = "host:unattributed", 0.0
    for name, s, e in spans:
        c = min(e, end) - max(s, start)
        if c > cover:
            best, cover = "host:" + name, c
    return best


def idle_pct(reduced):
    """The device's idle share of the traced window, in percent."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def kernel_seconds(reduced, needle):
    """Seconds (per device) of the operations whose own name holds
    ``needle`` and how many such operations there were; (None, 0) where
    none ran."""
    hit = {k: v for k, v in reduced["ops"].items() if needle in k}
    if not hit:
        return None, 0
    return sum(hit.values()), len(hit)
