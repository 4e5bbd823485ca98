"""Device time by ``jax.named_scope``, from the same ``.xplane.pb`` that
``trace_reduce`` reads.

``trace_reduce.op_name`` keeps an operation's own instruction name
(``fusion:<shape>``, ``pallas:<function>``): the scope a model put around
its layers (``hetu_tpu.graph.node.name_scope``: ``mix.ssm``, ``mlp`` …) is
not in it.  The profiler does record it: each event of a TPU plane's ``XLA
Ops`` line points at an event-metadata entry whose stats hold the
operation's framework name under ``tf_op`` — jax's ``op_name``, the path of
scopes the operation was traced under
(``jit(step)/mix.ssm/dot_general``).  ``jax.profiler.ProfileData`` shows an
event's own stats but not its metadata's, so this file reads those few
fields of the protobuf itself (``tsl/profiler/protobuf/xplane.proto``:
XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata =
5; XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value
= 5, .ref_value = 7) and joins them to ``trace_reduce``'s events by the
metadata's name, which is the event's name.  A runtime that writes no such
stat gives an empty table, and the readers return None.
"""
import functools
import os
import re

from . import trace_reduce


def _varint(buf, at):
    out = shift = 0
    while True:
        byte = buf[at]
        at += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, at
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: ints for varints, bytes
    for length-delimited fields; fixed-width fields are skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 1:
            value, at = None, at + 8
        elif wire == 5:
            value, at = None, at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield number, value


def _map_value(entry):
    """The value message of a protobuf map entry (key = 1, value = 2)."""
    for number, value in _fields(entry):
        if number == 2:
            return value
    return b""


def framework_names(path):
    """``{plane name: {event name: framework op name}}`` for the TPU
    planes of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, metas, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                metas.append(_map_value(value))
            elif number == 5:
                sid = sname = None
                for n, v in _fields(_map_value(value)):
                    if n == 1:
                        sid = v
                    elif n == 2:
                        sname = bytes(v).decode()
                stat_names[sid] = sname
        if not trace_reduce._DEVICE.match(name):
            continue
        table = out.setdefault(name, {})
        for meta in metas:
            ev_name, op = None, None
            for n, v in _fields(meta):
                if n == 2:
                    ev_name = bytes(v).decode()
                elif n == 5:
                    stat = dict((k, x) for k, x in _fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        op = bytes(stat[5]).decode()
                    elif 7 in stat:
                        op = stat_names.get(stat[7])
            if ev_name and op:
                table[ev_name] = op
    return out


@functools.lru_cache(maxsize=1)
def _parsed(path, stamp):
    """One parse of a trace file (some hundred MB), however many readers
    ask: ``(framework names, trace_reduce's planes)``."""
    return framework_names(path), trace_reduce.load(path)


def scope_seconds(path, scopes):
    """``{scope: seconds}`` of device time (self times, so that nested
    events count once) inside the benchmark's window for each of
    ``scopes`` — a scope owns an operation when it is a component of the
    operation's framework name — plus ``"busy"``; None when the trace
    names no operation's scope."""
    names, planes = _parsed(path, os.stat(path).st_mtime_ns)
    if not any(names.values()):
        return None
    lo, hi = trace_reduce.window_of(planes)
    rx = {s: re.compile(r"(^|/)" + re.escape(s) + r"(/|$)") for s in scopes}
    out, busy, devices = dict.fromkeys(scopes, 0.0), 0.0, 0
    for pname, table in names.items():
        events = trace_reduce._clip(
            planes.get(pname, {}).get(trace_reduce._OPS_LINE, []), lo, hi)
        if not events:
            continue
        devices += 1
        busy += sum(b - a for a, b in trace_reduce._union(events))
        # each event under the scope that owns it (scopes do not nest in
        # one another), so that the reducer's own self times — a nested
        # event's time taken from the event that contains it — add up
        labelled = [(_owner(table.get(text, ""), rx), a, b)
                    for text, a, b in events]
        for scope, ns in trace_reduce.self_times(labelled).items():
            if scope in out:
                out[scope] += ns
    if not devices:
        return None
    out = {k: v / devices / 1e9 for k, v in out.items()}
    out["busy"] = busy / devices / 1e9
    return out


def _owner(op, rx):
    return next((s for s, pattern in rx.items() if pattern.search(op)), "-")


def of_run(run, scopes):
    """:func:`scope_seconds` of the traced run ``run`` (the harness's run
    object): the tracer writes under ``.bench_out/trace/<workload>`` of
    the checkout, and the workload is the configuration's name and the
    mix's.  None when the run was not traced there."""
    if run.get("trace") is None or "name" not in run.get("mix", {}):
        return None
    from . import harness
    workload = f"{run['cfg']['name']}.{run['mix']['name']}"
    try:
        path = trace_reduce.find_xplane(os.path.join(
            harness.ROOT, ".bench_out", "trace", workload))
    except FileNotFoundError:
        return None
    return scope_seconds(path, scopes)


def share(run, scopes):
    """Device time under any of ``scopes`` as a share of busy time, in
    percent; None when the trace does not say."""
    got = of_run(run, scopes)
    if not got or got["busy"] <= 0:
        return None
    return 100.0 * sum(got[s] for s in scopes) / got["busy"]
