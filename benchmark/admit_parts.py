"""The admit plans by part, for the ``admit_*`` readers.

An admission is one program (``XLA Modules`` events ``^jit_admit``).
The program names its parts with ``jax.named_scope``s, listed in one
place as ``analytics_zoo_tpu.observability.profile.ADMIT_PARTS``: the
table lookups, norms and residual adds, q/k/v/o projections, the
attention over the prompt, a dense MLP, the router, the held experts,
the shared experts, a Mamba mixer's projections, its convolution and
its scan, the head, the slot writes and the first token's pick.
``split`` takes the operations of the admit programs that ran wholly
inside the traced window and gives each operation's time, less what
runs nested in it (``Spans.scope_seconds``'s rule, ``xplane.
self_times``), to the innermost of those names in its scope path; time
under none of them is ``"unnamed"``.

A scope path is the program's where it starts ``jit(``.  The TPU
compiler writes its own metadata on some operations it makes: the
grouped products of ``lax.ragged_dot`` become Mosaic kernels named
``ragged-dot-none``, memory moves and multi-output fusions carry none.
Such an operation takes the part of the first later operation of the
same program that reads its result (the operands in the event's name,
``%name``), through any chain of such operations.  A multi-output
fusion's readers name the tuple's elements and not the fusion, so one
that no reader names takes the part of the first of its own operands
that has one; what neither reaches is ``"unnamed"``.  The admissions'
own lengths are
the ``length`` stat of the traced ``zoo/decode/admit`` spans, their
buckets the ``bucket`` stat: padding to the bucket is work the floors
below leave out, so it reads low in every ``*_mxu_roofline``.

On a program without the partition (no ``ADMIT_PARTS``: the parent of
the change that brought it), a trace with no whole admit program, or
one whose admit programs hold none of the names, ``split`` is ``None``
and so is every reader.

The floors (least device seconds of one admission of a prompt of its
own length ``n``), by family, from the ``costs*.py`` helpers:
``attention_flops`` (4 h hd per visible (query, key) pair of each
attention layer, a window where the layer has one), ``experts_floor_s``
(the held experts' routed pairs' flops over the MXU's peak or their
weights' bytes over the HBM's, whichever is longer) and ``dense_flops``
(2 n x the weights a token multiplies by in the projections, MLPs,
router and shared experts, and the head at the last position alone)."""

import bisect
import functools
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List

from benchmark import costs, program_spans, xplane

UNNAMED = "unnamed"
PROGRAMS = re.compile(r"^jit_admit")
#: the parts each ``admit_*`` reader reads
ATTN_CORE = "zoo_attn_core"
EXPERTS = "zoo_moe_experts"
DENSE = ("zoo_attn_proj", "zoo_mlp", "zoo_moe_shared", "zoo_moe_router",
         "zoo_ssm_proj", "zoo_head")


@dataclass
class Split:
    """``seconds``: ``{part: device seconds}`` over the whole admit
    programs, ``"unnamed"`` among them; ``programs_s``: those programs'
    own device time; ``count``: how many; ``lengths`` / ``buckets``: of
    the traced ``zoo/decode/admit`` spans."""
    seconds: Dict[str, float]
    programs_s: float
    count: int
    lengths: List[int] = field(default_factory=list)
    buckets: List[int] = field(default_factory=list)

    def share(self, part):
        return 100.0 * self.seconds.get(part, 0.0) / self.programs_s


def part_names():
    """The program's ``ADMIT_PARTS``, or ``None`` where it has none."""
    try:
        from analytics_zoo_tpu.observability import profile
    except ImportError:
        return None
    names = getattr(profile, "ADMIT_PARTS", None)
    return tuple(names) if names else None


@functools.lru_cache(maxsize=None)
def innermost(path, names):
    """The name of ``names`` that ``path`` holds last (bare or inside
    ``jit(...)``, as ``program_spans.in_scope`` matches one), or
    ``"unnamed"``."""
    best, at = UNNAMED, -1
    for name in names:
        for m in re.finditer(rf"(^|[/(]){re.escape(name)}(?=[/)]|$)",
                             path):
            if m.start() > at:
                best, at = name, m.start()
    return best


_OPERANDS_END = re.compile(r", [a-z_]+=")      # the first attribute
_INSTRUCTION = re.compile(r"%[\w.\-]+")


@functools.lru_cache(maxsize=None)
def instruction(event_name):
    """``(own name, operand names)`` of an operation's event, named by
    its HLO line: ``%fusion.9 = bf16[8]{0} fusion(%a, %b), kind=...``
    -> ``("%fusion.9", ("%a", "%b"))``."""
    own, _, rest = event_name.partition(" = ")
    own = own.split()[-1] if own.split() else own
    return own, tuple(_INSTRUCTION.findall(_OPERANDS_END.split(rest, 1)[0]))


def program_parts(ops, names):
    """The part of each ``(event name, scope path)`` of ONE run of a
    program, in the order they started: the innermost part name of a
    path the program wrote; for an operation the compiler named, the
    part of the first later operation that reads its result, else that
    of the first of its operands that has one."""
    parts, waiting = [], {}         # waiting: own name -> [indices]
    last, reads = {}, []            # own name -> index; operands by index
    for i, (event, path) in enumerate(ops):
        own, operands = instruction(event)
        part = innermost(path, names) if path.startswith("jit(") else None
        for name in operands:
            for j in waiting.pop(name, ()):
                if part is None:
                    waiting.setdefault(own, []).append(j)
                else:
                    parts[j] = part
        if part is None:
            waiting.setdefault(own, []).append(i)
        parts.append(part)
        reads.append([last[n] for n in operands if n in last])
        last[own] = i
    for i, part in enumerate(parts):
        if part is None:
            parts[i] = next((parts[j] for j in reads[i] if parts[j]), None)
    return [UNNAMED if p is None else p for p in parts]


@functools.lru_cache(maxsize=4)
def _split(spans, names):
    whole = {}
    for plane, name, lo, hi, _ in spans.modules:
        if PROGRAMS.match(name) and lo > spans.lo and hi < spans.hi:
            whole.setdefault(plane, []).append((lo, hi))
    if not whole:
        return None
    seconds = {}
    for plane, programs in whole.items():
        programs.sort()
        starts = [lo for lo, _ in programs]
        runs = [[] for _ in programs]
        for p, event, lo, hi, path in spans.ops:
            if p != plane:
                continue
            k = bisect.bisect_right(starts, lo) - 1
            if k < 0 or hi > programs[k][1]:
                continue
            runs[k].append((event, path, lo, hi))
        rows = []
        for run in runs:
            parts = program_parts([(e, path) for e, path, _, _ in run],
                                  names)
            rows += [(part, lo, hi - lo)
                     for part, (_, _, lo, hi) in zip(parts, run)]
        for part, s in xplane.self_times(rows).items():
            seconds[part] = seconds.get(part, 0.0) + s
    if not set(seconds) - {UNNAMED}:
        return None         # programs compiled without the names
    admits = spans.named("decode/admit")
    out = Split(seconds,
                sum(hi - lo for p in whole.values() for lo, hi in p) / 1e9,
                sum(len(p) for p in whole.values()),
                [e[4]["length"] for e in admits if "length" in e[4]],
                [e[4]["bucket"] for e in admits if "bucket" in e[4]])
    report(out)
    return out


def split(ctx):
    """The traced run's admit programs by part, or ``None``."""
    names, spans = part_names(), program_spans.of_run(ctx)
    if names is None or spans is None:
        return None
    return _split(spans, names)


def report(s, file=None):
    """The table, once a traced run: part, seconds, share of the admit
    programs; the parts' sum against the programs' time; padding's share
    of the traced admissions' positions."""
    file = file or sys.stderr
    print(f"admit_parts: {s.count} whole admit programs, "
          f"{s.programs_s:.6f} s on the device", file=file)
    for part, sec in sorted(s.seconds.items(), key=lambda kv: -kv[1]):
        print(f"admit_parts: {part:16s} {sec:.6f} s {s.share(part):7.3f} %",
              file=file)
    total = sum(s.seconds.values())
    print(f"admit_parts: parts sum {total:.6f} s = "
          f"{100.0 * total / s.programs_s:.3f} % of the programs", file=file)
    if s.lengths and s.buckets:
        pad = 1.0 - sum(s.lengths) / sum(s.buckets)
        print(f"admit_parts: {len(s.lengths)} traced admissions, mean "
              f"length {sum(s.lengths) / len(s.lengths):.1f}, padding "
              f"{100.0 * pad:.3f} % of the bucket positions", file=file)
    file.flush()


# ------------------------------------------------ floors, by family
def _family(ctx):
    return ctx["workload"]["adapter"]


def attention_flops(ctx, n):
    """The causal attention over a prompt of ``n``: 4 h hd per visible
    (query, key) pair of each attention layer."""
    cfg, family = ctx["config"], _family(ctx)
    if family == "cohere2moe":
        from benchmark import costs_cohere2moe as c2
        c = c2.dims(cfg)
        pairs = (c["full"] * c2.visible_keys(n)
                 + c["sliding"] * c2.visible_keys(n, c["window"]))
        return 4 * c["h"] * c["hd"] * pairs
    if family == "granitehybrid":
        from benchmark import costs_granitehybrid as gh
        c = gh.dims(cfg)
        return c["attention"] * 4 * c["h"] * c["hd"] * n * (n + 1) // 2
    return cfg["n_layer"] * costs.causal_attention_flops(n, cfg["n_embd"])


def experts_floor_s(ctx, n, peaks):
    """The held experts of every layer over a prompt of ``n``: the
    (token, held expert) pairs' flops, ``held_pairs_per_token`` a token
    for uniform routing, over the MXU's peak, or every held expert's
    weights read once over the HBM's, whichever is longer."""
    from benchmark import costs_cohere2moe as c2
    cfg = ctx["config"]
    c = c2.dims(cfg)
    flops = (2 * c2.held_pairs_per_token(cfg) * c2.expert_params(cfg)
             * c["layers"] * n)
    nbytes = c2.ITEM * c["held"] * c2.expert_params(cfg) * c["layers"]
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def dense_flops(ctx, n):
    """2 n x the weights a token multiplies by outside attention's core
    and the held experts (q/k/v/o, MLPs, router, shared experts, a Mamba
    mixer's in and out projections), and 2 d vocab for the head at the
    last position."""
    cfg, family = ctx["config"], _family(ctx)
    if family == "cohere2moe":
        from benchmark import costs_cohere2moe as c2
        c = c2.dims(cfg)
        per_token = c["layers"] * (c2.attention_params(cfg)
                                   + c["d"] * c["published"]
                                   + c["shared"] * c2.expert_params(cfg))
        return 2 * n * per_token + 2 * c["d"] * c["vocab"]
    if family == "granitehybrid":
        from benchmark import costs_granitehybrid as gh
        c = gh.dims(cfg)
        return (2 * n * gh.matmul_params_per_token(cfg)
                + 2 * c["d"] * c["vocab"])
    head = cfg["n_embd"] * cfg["vocab_size"]
    return 2 * n * (costs.lm_matmul_params(cfg) - head) + 2 * head


def roofline(ctx, parts, floor_s_of):
    """The least time of the traced window's whole admissions (each at
    the mean of the traced lengths' floors) over the device time of
    ``parts``, in percent; ``None`` where there is nothing to read."""
    s = split(ctx)
    if s is None or not s.lengths:
        return None
    seconds = sum(s.seconds.get(p, 0.0) for p in parts)
    if not seconds:
        return None
    floor_s = sum(floor_s_of(n) for n in s.lengths) / len(s.lengths)
    return 100.0 * s.count * floor_s / seconds
