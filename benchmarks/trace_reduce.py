"""From a profiler trace to numbers: busy and idle time, device time by
operation, and the longest idle gaps with what the host was doing.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and nothing else.  A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed
operation (a ``while`` or a fusion's event spans the events of what it
contains), so busy time is the UNION of the intervals and an operation's own
time is its interval less what its children cover.  Host planes hold the
``TraceAnnotation`` spans; the benchmark marks its traced window with one
named :data:`WINDOW`.

Checked on the small recorded trace beside this file
(``trace_fixture.xplane.pb``, ``tests/bench_harness``).
"""
import glob
import os
import re

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path):
    """``{plane name: {line name: [(event name, start_ns, dur_ns), ...]}}``
    (lines of one name on one plane are merged)."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return planes


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_name(text):
    """One name per kind of operation, from the event's text.  On a TPU an
    event of the ``XLA Ops`` line is named by its whole HLO instruction
    (``%copy.11 = bf16[512,512]{...} copy(...)``): the name is what stands
    before `` = `` without ``%`` and the trailing ``.<n>``.  A Pallas kernel
    (a custom call to ``tpu_custom_call``) becomes ``pallas:<name>`` — its
    instruction is named after the traced function, not the kernel, so the
    target is the one stable mark.  A fusion XLA left unnamed becomes
    ``fusion:<first result shape>``, which is what tells the big ones
    apart."""
    head, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    if "tpu_custom_call" in rest:
        return "pallas:" + name
    if name == "fusion":
        shape = _SHAPE.search(rest)
        return "fusion:" + shape.group(0) if shape else name
    return name


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Sorted, merged ``[(a, b)]`` of ``[(name, a, b)]``."""
    merged = []
    for _, a, b in sorted(intervals, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def self_times(intervals):
    """``{op name: ns}`` where a nested event's time is taken from the
    event that contains it, so the values add up to the busy time."""
    out = {}
    stack = []      # [name, end, child_ns]

    def close(entry, begin):
        name, end, child = entry
        out[name] = out.get(name, 0.0) + (end - begin) - child

    begins = []
    for name, a, b in sorted(intervals, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop(), begins.pop())
        b = min(b, stack[-1][1]) if stack else b
        if stack:
            stack[-1][2] += b - a
        stack.append([op_name(name), b, 0.0])
        begins.append(a)
    while stack:
        close(stack.pop(), begins.pop())
    return out


def _host_events(planes):
    for pname, lines in planes.items():
        if _DEVICE.match(pname):
            continue
        for events in lines.values():
            yield from events


def window_of(planes):
    """(start_ns, end_ns) of the benchmark's marked window, else the span
    of all device operations."""
    marks = [(s, s + d) for n, s, d in _host_events(planes) if n == WINDOW]
    if marks:
        return max(marks, key=lambda m: m[1] - m[0])
    spans = [(s, s + d) for p, lines in planes.items() if _DEVICE.match(p)
             for n, s, d in lines.get(_OPS_LINE, [])]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(planes, top=10):
    """The numbers the benchmark reports from one trace::

        {"window_s", "busy_s", "devices", "op_s": {name: s},
         "device_ops": [[name, s], ...], "idle_gaps": [[owner, s], ...]}

    ``busy_s`` and ``op_s`` are averaged over the devices that ran
    anything; the gaps are those of the first such device."""
    lo, hi = window_of(planes)
    per_device = []
    for pname in sorted(planes):
        if not _DEVICE.match(pname):
            continue
        ops = _clip(planes[pname].get(_OPS_LINE, []), lo, hi)
        if ops:
            per_device.append(ops)
    if not per_device:
        raise ValueError("no operation ran on a device inside the window")
    n = len(per_device)
    busy, op_ns = 0.0, {}
    for ops in per_device:
        busy += sum(b - a for a, b in _union(ops))
        for name, ns in self_times(ops).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
    op_s = {k: v / n / 1e9 for k, v in op_ns.items()}
    host = [(nm, s, s + d) for nm, s, d in _host_events(planes)
            if s < hi and s + d > lo]
    gaps, at = [], lo
    for a, b in _union(per_device[0]) + [[hi, hi]]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        owners = [(e - s, nm) for nm, s, e in host if s <= mid < e]
        idle.append([min(owners)[1] if owners else WINDOW, (b - a) / 1e9])
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "devices": n, "op_s": op_s,
            "device_ops": [[k, v] for k, v in ranked], "idle_gaps": idle}


def share(red, pattern):
    """Device time of operations whose name matches ``pattern`` (a regular
    expression, searched) as a share of busy time, in percent; None when no
    such operation ran."""
    rx = re.compile(pattern)
    hit = [v for k, v in red["op_s"].items() if rx.search(k)]
    if not hit or red["busy_s"] <= 0:
        return None
    return 100.0 * sum(hit) / red["busy_s"]
