"""Decode model step: the least time the chip could take for the chunked
scans of the traced window's admissions over the device time of the
operations under the scope ``zoo_ssm_scan`` inside the admit programs
(scope paths ``jit(admit)/...``) that ran wholly inside the trace.  A
scan of one layer over a prompt of its own length (the traced
``zoo/decode/admit`` spans' ``length``) needs ``costs_granitehybrid.
scan_flops`` over the bf16 peak or ``scan_bytes`` over the HBM peak,
whichever is longer: in each chunk ``C B^T`` and its masked product with
``dt x``, the chunk states and what they give the next chunk.  Padding
to the bucket is work the count leaves out."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
SCOPE = "zoo_ssm_scan"
PROGRAMS = re.compile(r"^jit_admit")
ADMIT_PATH = re.compile(r"^jit\(admit")


def read(ctx):
    from benchmark import costs_granitehybrid as costs
    from benchmark import program_spans
    spans, cfg = program_spans.of_run(ctx), ctx["config"]
    if spans is None:
        return None
    lengths = [e[4]["length"] for e in spans.named("decode/admit")
               if "length" in e[4]]
    whole = [(lo, hi) for _, name, lo, hi, _ in spans.modules
             if PROGRAMS.match(name) and lo > spans.lo and hi < spans.hi]
    if not lengths or not whole:
        return None
    inside = program_spans.Spans(
        (spans.lo, spans.hi), spans.host,
        [e for e in spans.ops if e[4] and ADMIT_PATH.match(e[4])
         and any(lo <= e[2] and e[3] <= hi for lo, hi in whole)],
        spans.modules)
    seconds = inside.scope_seconds(SCOPE)
    if not seconds:
        return None
    layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count("mamba")
    floor_s = layers * sum(costs.scan_floor_s(cfg, n, ctx["peaks"])
                           for n in lengths) / len(lengths)
    return 100.0 * len(whole) * floor_s / seconds
