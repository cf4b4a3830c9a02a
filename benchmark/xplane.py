"""Reduction of a profiler trace (``*.xplane.pb``) to what the
per-layer metrics read: device busy intervals and idle share, device
time per program and per operation, and the longest idle gaps named by
the benchmark's own host spans.  Read with ``jax.profiler.ProfileData``;
nothing here imports the program.

What a TPU v5e trace of this jax looks like (looked at by hand, PR 25):
one plane per chip named ``/device:TPU:<n>``; its line ``XLA Modules``
holds one event per executed program, named ``jit_<fn>(<fingerprint>)``;
its line ``XLA Ops`` holds one event per HLO operation, nested where an
operation (a ``while``) runs others inside it.  Host threads are lines
of the plane ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an
event on the line of the thread that wrote it.  All times are
nanoseconds on one clock."""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"          # the benchmark's own TraceAnnotations
WINDOW_SPAN = "bench/traced"    # the traced window itself: names no gap


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    """Rows ``(plane, line, name, start_ns, duration_ns)`` of the device
    planes' operation and program lines and of the benchmark's own host
    spans.  ``path`` is an ``.xplane.pb`` or a trace directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    rows.append((plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return rows


def union_seconds(intervals):
    """Total length of the union of ``(start_ns, end_ns)`` intervals, in
    seconds, and the merged intervals."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return sum(hi - lo for lo, hi in merged) / 1e9, merged


def self_times(events):
    """``{name: seconds}`` of ``(name, start, duration)`` events of ONE
    line, each event's time less the events nested inside it, so a
    ``while`` does not count its body twice."""
    out, stack = {}, []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0) / 1e9

    for name, lo, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, lo + dur, dur])
    close(float("inf"))
    return out


def program_name(event_name):
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name):
    """An operation's event is named by its whole HLO line,
    ``%fusion.12 = f32[..] fusion(...), kind=kLoop, ...``; keep
    ``%fusion.12``, and for a fusion its kind."""
    short = event_name.split(" = ", 1)[0]
    kind = re.search(r"kind=(k\w+)", event_name)
    return f"{short} {kind.group(1)}" if kind else short


def is_pallas_call(event_name):
    """A pallas (Mosaic) kernel is a custom call to ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in event_name


def reduce(rows, lo_ns=None, hi_ns=None):
    """The trace as numbers.  ``lo_ns``/``hi_ns`` clip to a window
    (default: first device event to last).  Returns ``None`` when no
    operation ran on a device.

    ``busy_s`` and ``window_s`` are averaged over the device planes;
    ``ops`` and ``programs`` are device seconds by name, summed over the
    planes (an operation's time less what runs nested in it);
    ``pallas_calls`` is ``{name: [seconds, calls]}`` of the pallas
    kernels; ``gaps`` are the idle intervals of the first device, longest
    first, each named by the shortest of the benchmark's host spans that
    covers half of it, or "unattributed"."""
    devices = sorted({r[0] for r in rows if DEVICE_PLANE.match(r[0])})
    ops = [r for r in rows if r[1] == OPS_LINE]
    if not devices or not ops:
        return None
    if lo_ns is None:
        lo_ns = min(r[3] for r in ops)
    if hi_ns is None:
        hi_ns = max(r[3] + r[4] for r in ops)

    def clip(r):
        lo, hi = max(r[3], lo_ns), min(r[3] + r[4], hi_ns)
        return (lo, hi) if hi > lo else None

    busy, op_s, prog_s, first_merged = [], {}, {}, None
    for dev in devices:
        spans = [c for r in ops if r[0] == dev for c in [clip(r)] if c]
        seconds, merged = union_seconds(spans)
        busy.append(seconds)
        if first_merged is None:
            first_merged = merged
        for line, sink, rename in ((OPS_LINE, op_s, op_name),
                                   (MODULES_LINE, prog_s, program_name)):
            evs = [(rename(r[2]), c[0], c[1] - c[0]) for r in rows
                   if r[0] == dev and r[1] == line
                   for c in [clip(r)] if c]
            for name, s in self_times(evs).items():
                sink[name] = sink.get(name, 0.0) + s
    kernels = {}
    for r in ops:
        c = clip(r)
        if c and is_pallas_call(r[2]):
            k = kernels.setdefault(op_name(r[2]), [0.0, 0])
            k[0] += (c[1] - c[0]) / 1e9
            k[1] += 1
    host = [(r[2], *c) for r in rows
            if r[0] == HOST_PLANE and r[2] != WINDOW_SPAN
            for c in [clip(r)] if c]
    gaps = []
    edges = [lo_ns] + [t for iv in first_merged for t in iv] + [hi_ns]
    for lo, hi in zip(edges[0::2], edges[1::2]):
        if hi <= lo:
            continue
        # the most specific (shortest) span that covers half the gap
        covering = [(s_hi - s_lo, name) for name, s_lo, s_hi in host
                    if min(hi, s_hi) - max(lo, s_lo) >= 0.5 * (hi - lo)]
        name = min(covering)[1] if covering else "unattributed"
        gaps.append((name, (hi - lo) / 1e9))
    return {"devices": len(devices),
            "window_s": (hi_ns - lo_ns) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "ops": op_s, "programs": prog_s, "pallas_calls": kernels,
            "gaps": sorted(gaps, key=lambda g: -g[1])}


def idle_share(trace):
    """1 - busy / window of a reduced trace, in percent; ``None`` where
    there is nothing to read."""
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def gaps_by_name(gaps):
    """Idle seconds summed by the host span's name, largest first."""
    out = {}
    for name, s in gaps:
        out[name] = out.get(name, 0.0) + s
    return sorted(out.items(), key=lambda kv: -kv[1])


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
