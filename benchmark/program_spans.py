"""The program's own names in a traced run: its host spans (``zoo/...``,
``analytics_zoo_tpu.observability.profile.SPANS``) with their stats and
their thread, and the device's operations with the kernel name and the
scope path of each, all clipped to ``bench/traced``.  The readers under
``layer_metrics/`` that go by those names share this module;
``xplane.py`` (which names nothing and loads only ``bench/`` spans)
stays as it is and lends ``union_seconds``, ``self_times`` and
``is_pallas_call``.

Read from ``<root>/.bench_trace``, where ``run.py`` puts the traced
run's ``.xplane.pb``, once per process (about 7 s for the chat cell's
1.4 million device events).  On a program that has no such span, kernel
name or scope (the parent of the PR that brought them), every reader
finds nothing and returns ``None``.

Where the names are found in a TPU v5e trace of this jax (looked at by
hand, PR 26).  Device plane ``/device:TPU:<n>``, line ``XLA Ops``: an
event's NAME is the whole HLO line without its metadata,
``%zoo_flash_fwd.24 = (f32[16,256,64]...) custom-call(...),
custom_call_target="tpu_custom_call", ...``, so a pallas kernel's
``name=`` is found in the instruction's name (inside ``grad`` it reads
``%jvp_zoo_flash_fwd_.N``, ``%transpose_jvp_zoo_flash_bwd_dq__.N``).
The event's own STATS hold its times and nothing else
(``device_offset_ps``, ``device_duration_ps``).  The scope path
(``jit(stepk)/while/body/closed_call/jit(zoo_sample)/vmap()/top_k:``,
the HLO metadata's ``op_name``) is a stat of the event's METADATA
(``XEventMetadata.stats``, named ``tf_op``), which
``jax.profiler.ProfileData`` does not show: ``op_scopes`` takes it from
the file's protobuf wire format, metadata only, stepping over the
events.  It is what the compiler wrote when the executable was BUILT: a
program answered from the persistent compilation cache carries the
scopes of whoever compiled it first (jax leaves metadata out of the
cache's key), which is why ``zoo_sample`` is a jit of its own inside the
decode plans and not a ``named_scope``.  Host plane ``/host:CPU``: one
line per thread, every Python thread's line named ``python3`` (so a
thread is known by its line's index); a ``TraceAnnotation``'s keyword
arguments come back as the event's stats."""

import bisect
import functools
import os
import re
import sys
import time

from benchmark import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

try:        # the names are the program's; a program without them has none
    from analytics_zoo_tpu.observability import profile as _names
    SPAN_PREFIX = _names.SPAN_PREFIX
except (ImportError, AttributeError):
    SPAN_PREFIX = "zoo/"

SCOPE_STAT = "tf_op"    # the event metadata's stat that holds the scope


def span(name):
    """``decode/admit`` -> ``zoo/decode/admit``."""
    return SPAN_PREFIX + name


def fields(buf):
    """``(field number, wire type, value)`` of one protobuf message: a
    varint as an int, a length-delimited field as a memoryview of its
    bytes, whole and unread (so a plane's lines are stepped over)."""
    at, end = 0, len(buf)
    while at < end:
        key = shift = 0
        while True:
            byte = buf[at]
            at += 1
            key |= (byte & 0x7F) << shift
            if byte < 0x80:
                break
            shift += 7
        number, kind = key >> 3, key & 7
        if kind in (0, 2):
            value = shift = 0
            while True:
                byte = buf[at]
                at += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            if kind == 2:
                value, at = buf[at:at + value], at + value
            yield number, kind, value
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            yield number, kind, buf[at:at + width]
            at += width
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def op_scopes(path, stat=SCOPE_STAT):
    """``{device plane: {event name: scope path}}`` from the planes'
    event METADATA, which ``ProfileData`` does not show: ``XSpace.planes
    = 1``; ``XPlane.name = 2``, ``.event_metadata = 4`` and
    ``.stat_metadata = 5`` (maps: key 1, value 2); ``XEventMetadata.name
    = 2``, ``.stats = 5``; ``XStat.metadata_id = 1``, ``.str_value = 5``,
    ``.ref_value = 7`` (a stat name's id); ``XStatMetadata.name = 2``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, plane in fields(space):
        if number != 1:
            continue
        name, metas, stat_names = None, [], {}
        for n, _, v in fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                metas.append(v)
            elif n == 5:
                entry = dict((k, x) for k, _, x in fields(v))
                stat_names[entry.get(1)] = "".join(
                    bytes(x).decode() for k, _, x in fields(entry[2])
                    if k == 2)
        if not name or not xplane.DEVICE_PLANE.match(name):
            continue
        wanted = {i for i, s in stat_names.items() if s == stat}
        scopes = out[name] = {}
        for entry in metas:
            meta = dict((k, x) for k, _, x in fields(entry)).get(2, b"")
            event, scope = None, None
            for n, _, v in fields(meta):
                if n == 2:
                    event = bytes(v).decode()
                elif n == 5:
                    st = dict((k, x) for k, _, x in fields(v))
                    if st.get(1) in wanted:
                        scope = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7)))
            if event and scope:
                scopes[event] = scope
    return out


def in_scope(path, scope):
    """Whether a scope path (``jit(stepk)/while/body/closed_call/
    jit(zoo_sample)/vmap()/top_k:``) holds ``scope`` as a name of its
    own, bare or inside ``jit(...)``, ``jvp(...)``, ``transpose(...)``."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", path) \
        is not None


class Spans:
    """A traced run under the program's names, clipped to its window.

    ``host``: ``(thread, name, lo_ns, hi_ns, stats)`` of every ``zoo/``
    event, ``thread`` the index of its line (threads may share a name).
    ``ops`` / ``modules``: ``(plane, name, lo_ns, hi_ns, scope)`` of the
    device lines' events, in the order of nesting (by start, the longer
    first)."""

    def __init__(self, window, host, ops, modules):
        self.lo, self.hi = window
        self.host, self.ops, self.modules = host, ops, modules

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9

    # ------------------------------------------------------ host spans
    def named(self, name):
        return [e for e in self.host if e[1] == span(name)]

    def thread_of(self, prefix):
        """The one thread whose spans start with ``zoo/<prefix>``, or
        ``None``."""
        threads = {e[0] for e in self.host
                   if e[1].startswith(span(prefix))}
        return threads.pop() if len(threads) == 1 else None

    def self_seconds(self, thread):
        """``{span name: seconds}`` of one thread, each span's time less
        its children's on that thread."""
        return xplane.self_times(
            [(name, lo, hi - lo) for t, name, lo, hi, _ in self.host
             if t == thread])

    def innermost(self, thread):
        """The thread's time under its spans, cut into ``(lo, hi,
        name)`` pieces that do not overlap, each named by the most
        specific span open there."""
        pieces, stack, at = [], [], None     # stack: (name, end)

        def close(upto):
            nonlocal at
            while stack and stack[-1][1] <= upto:
                name, end = stack.pop()
                if end > at:
                    pieces.append((at, end, name))
                    at = end
            if stack and upto > at:
                pieces.append((at, upto, stack[-1][0]))

        for _, name, lo, hi, _ in sorted(
                (e for e in self.host if e[0] == thread),
                key=lambda e: (e[2], -e[3])):
            if stack:
                close(lo)
            at = lo
            stack.append((name, hi))
        close(float("inf"))
        return pieces

    # -------------------------------------------------------- the device
    def devices(self):
        return sorted({e[0] for e in self.ops})

    def busy(self, plane=None):
        """Seconds in which an operation ran, and the merged intervals,
        on one device plane (default: the first)."""
        plane = plane or self.devices()[0]
        return xplane.union_seconds(
            [(lo, hi) for p, _, lo, hi, _ in self.ops if p == plane])

    def kernel_seconds(self, kernel):
        """``(device seconds, calls)`` of the pallas calls whose
        instruction name holds ``kernel``, the ``name=`` given to
        ``pallas_call`` (under ``grad`` the instruction is
        ``%transpose_jvp_<name>__.N``)."""
        mine = [hi - lo for _, name, lo, hi, _ in self.ops
                if kernel in name       # cheap, before the line is cut
                and kernel in name.split(" = ", 1)[0]
                and xplane.is_pallas_call(name)]
        return sum(mine) / 1e9, len(mine)

    def scope_seconds(self, scope):
        """Device seconds of the operations whose scope path holds
        ``scope``, each operation's time less what runs nested in it,
        summed over the devices."""
        hit = {}
        for e in self.ops:      # a few thousand distinct paths
            if e[4] not in hit:
                hit[e[4]] = bool(e[4]) and in_scope(e[4], scope)
        total = 0.0
        for plane in self.devices():
            ops = [e for e in self.ops if e[0] == plane]
            starts = [e[2] for e in ops]
            for k, (_, _, lo, hi, path) in enumerate(ops):
                if not hit[path]:
                    continue
                # with what runs inside it, which starts before it ends
                nested = ops[k:bisect.bisect_left(starts, hi, k)]
                if k and hit[ops[k - 1][4]] and ops[k - 1][3] >= hi:
                    continue    # counted with the operation around it
                total += xplane.self_times(
                    [(scope if hit[p] else "", a, b - a)
                     for _, _, a, b, p in nested]).get(scope, 0.0)
        return total

    def idle_by_span(self, thread):
        """``{name: seconds}`` of the first device's idle time in the
        window, by the most specific span of ``thread`` open at that
        moment; what no span covers is ``"unattributed"``."""
        _, merged = self.busy()
        edges = [self.lo] + [t for iv in merged for t in iv] + [self.hi]
        pieces = self.innermost(thread)
        out, k = {}, 0
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi <= lo:
                continue
            rest = hi - lo
            while k < len(pieces) and pieces[k][1] <= lo:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < hi:
                p_lo, p_hi, name = pieces[j]
                part = min(hi, p_hi) - max(lo, p_lo)
                out[name] = out.get(name, 0.0) + part / 1e9
                rest -= part
                j += 1
            if rest > 0:
                out["unattributed"] = out.get("unattributed", 0.0) \
                    + rest / 1e9
        return out


def trace_events(path):
    """``(plane, line, name, lo_ns, hi_ns, extra)`` of an ``.xplane.pb``:
    first the host plane's ``bench/traced`` and ``zoo/`` events (``line``
    the thread line's index, ``extra`` the event's stats), then the
    device planes' ``XLA Ops`` and ``XLA Modules`` events (``line`` the
    line's name, ``extra`` the operation's scope path)."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    for plane in planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name
                if name == xplane.WINDOW_SPAN or name.startswith(
                        SPAN_PREFIX):
                    yield (plane.name, thread, name, ev.start_ns,
                           ev.start_ns + ev.duration_ns, dict(ev.stats))
    scopes = op_scopes(path)
    for plane in planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        scope_of = scopes.get(plane.name, {}).get
        for line in plane.lines:
            if line.name not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                continue
            for ev in line.events:
                name, lo = ev.name, ev.start_ns
                yield (plane.name, line.name, name, lo,
                       lo + ev.duration_ns, scope_of(name, ""))


def build(events):
    """The ``Spans`` of ``trace_events``' rows (the host plane's first),
    everything clipped to the ``bench/traced`` span; ``None`` without
    one."""
    window, host = None, []
    lines = {xplane.OPS_LINE: [], xplane.MODULES_LINE: []}
    nested = True       # rows come by start, the longer first
    lo_w = hi_w = prev = None
    for plane, line, name, lo, hi, extra in events:
        if plane == xplane.HOST_PLANE:
            if name == xplane.WINDOW_SPAN:
                window = lo_w, hi_w = lo, hi
            elif name.startswith(SPAN_PREFIX):
                host.append((line, name, lo, hi, extra))
            continue
        if window is None:
            return None
        if hi <= lo_w or lo >= hi_w:
            continue
        row = (plane, name, max(lo, lo_w), min(hi, hi_w), extra)
        if prev is not None and (plane, line) == prev[:2] and (
                lo < prev[2] or (lo == prev[2] and hi > prev[3])):
            nested = False
        prev = (plane, line, lo, hi)
        lines[line].append(row)
    if window is None:
        return None
    if not nested:
        for rows in lines.values():
            rows.sort(key=lambda r: (r[0], r[2], -r[3]))
    host = [(t, name, max(lo, lo_w), min(hi, hi_w), stats)
            for t, name, lo, hi, stats in host if hi > lo_w and lo < hi_w]
    return Spans(window, host, lines[xplane.OPS_LINE],
                 lines[xplane.MODULES_LINE])


def parse(path):
    """The ``Spans`` of one ``.xplane.pb`` or trace directory."""
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    return build(trace_events(path))


@functools.lru_cache(maxsize=None)
def read_once(trace_dir):
    """``parse`` of the newest trace under ``trace_dir``, once per
    process; ``None`` where no traced run wrote one."""
    t0 = time.perf_counter()
    try:
        spans = parse(xplane.find_xplane(trace_dir))
    except FileNotFoundError:
        spans = None
    print(f"program_spans: read {trace_dir} in "
          f"{time.perf_counter() - t0:.2f} s", file=sys.stderr, flush=True)
    return spans


def of_run(ctx):
    """The traced run's ``Spans``: ``ctx["program_spans"]`` where a test
    gives one, else what ``run.py`` left under ``<root>/.bench_trace``."""
    if "program_spans" in ctx:
        return ctx["program_spans"]
    return read_once(TRACE_DIR)


# ------------------------------------------------- the flash kernels' work
#: big tensors of b*s*d elements each kernel reads and writes: forward
#: reads q, k, v and writes o; dq reads q, k, v, do and writes dq; dkv
#: reads q, k, v, do and writes dk, dv.  (The per-row statistics lse and
#: delta, 4 bytes a head and row, are under 2 % of these and left out,
#: as ``costs.flash_bytes`` leaves them out.)
FLASH_TENSORS = {"zoo_flash_fwd": 4, "zoo_flash_bwd_dq": 5,
                 "zoo_flash_bwd_dkv": 6}


def flash_kernel_roofline(ctx, kernel):
    """The least time the chip could take for ONE flash kernel's work
    over the device time its events took, in percent.

    The work, per call (one layer, one microbatch of ``b`` rows): a
    third of ``costs.flash_flops``, which is ``b *
    causal_attention_flops``: each of the three kernels owes two of the
    six matmuls (forward: QK^T, PV; dq: dO V^T, dS K; dkv: P^T dO, dS^T
    Q), the causal half counted once, and the scores that the backward
    kernels compute again are recomputation and not counted.  Bytes:
    the kernel's own reads and writes, ``FLASH_TENSORS`` of ``b*s*d``
    elements: 4 + 5 + 6 where ``costs.flash_bytes`` counts 12 for a
    backward that reads q, k, v once; so the three floors sum to more
    than the triple's when bytes bound a kernel.  Counts the work, not
    the implementation.  ``None`` where the trace names no such
    kernel."""
    from benchmark import costs
    spans = of_run(ctx)
    if spans is None:
        return None
    seconds, calls = spans.kernel_seconds(kernel)
    if not seconds:
        return None
    traffic, cfg = ctx["workload"]["traffic"], ctx["config"]
    micro = traffic["batch"] // traffic.get("accum_steps", 1)
    seq, d = traffic["seq_len"], cfg["n_embd"]
    flops = micro * costs.causal_attention_flops(seq, d)
    nbytes = FLASH_TENSORS[kernel] * micro * seq * d * 2
    floor_s = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    print(f"{kernel}: {calls} calls, {seconds:.4f} s on the device, "
          f"least {floor_s * 1e6:.1f} us a call "
          f"({'flops' if flops / ctx['peaks']['bf16_flops_per_s'] >= floor_s else 'bytes'} bound)",
          file=sys.stderr, flush=True)
    return 100.0 * calls * floor_s / seconds
