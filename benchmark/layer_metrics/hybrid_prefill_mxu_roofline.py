"""Decode model step: the least time the chip could take for the traced
window's admissions over the device time it took for them.  An
admission is one program (``XLA Modules`` events ``^jit_admit``: the
prompt's forward through every layer, its states laid into the slot,
the first token picked); it NEEDS the flops of its prompt's own length
(``costs_granitehybrid.prefill_flops``: projections, MLPs, the
convolution, each Mamba layer's chunked scan, every (query, visible
key) pair of the attention layers, the head for the last position) over
the bf16 peak.  The lengths are the program's own: the ``length`` stat
of the traced ``zoo/decode/admit`` spans.  Only admissions whose program
ran wholly inside the traced window count; padding to the bucket is
work the count leaves out, so it reads low here."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_admit")


def read(ctx):
    from benchmark import costs_granitehybrid as costs
    from benchmark import program_spans
    spans, cfg = program_spans.of_run(ctx), ctx["config"]
    if spans is None:
        return None
    lengths = [e[4]["length"] for e in spans.named("decode/admit")
               if "length" in e[4]]
    whole = [hi - lo for _, name, lo, hi, _ in spans.modules
             if PROGRAMS.match(name) and lo > spans.lo and hi < spans.hi]
    if not lengths or not whole:
        return None
    flops = sum(costs.prefill_flops(cfg, n) for n in lengths) / len(lengths)
    floor_s = flops / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * len(whole) * floor_s / (sum(whole) / 1e9)
