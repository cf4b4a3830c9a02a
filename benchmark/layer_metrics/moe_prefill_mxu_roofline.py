"""Decode model step: the least time the chip could take for the traced
window's admissions over the device time it took for them.  An
admission of the ``cohere2_moe`` family is one program (``XLA Modules``
events ``^jit_admit``: the prompt's forward through every layer, its
rows laid into the slabs, the first token picked); it NEEDS the flops
of its prompt's own length (``costs_cohere2moe.prefill_flops``:
projections, router, shared experts, the held experts its tokens were
routed to in expectation, every (query, visible key) pair of each
layer's window, the head for the last position), over the bf16 peak:
compute binds a prompt of thousands of tokens.  The lengths are the
program's own: the ``length`` stat of the traced ``zoo/decode/admit``
spans.  Only admissions whose program ran wholly inside the traced
window count.  Padding to the bucket, rows of experts not held and a
window's masked tiles are work the count leaves out, so they read
low here."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_admit")


def read(ctx):
    from benchmark import costs_cohere2moe as costs
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
