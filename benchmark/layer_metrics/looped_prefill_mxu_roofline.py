"""Decode model step of a looped model: the least time the chip could
take for the traced window's admissions over the device time it took for
them.  An admission is one program (``XLA Modules`` events
``^jit_admit``: the prompt through every pass of every layer, each
pass's keys and values laid into the slot, the first token picked); it
NEEDS the flops of its prompt's own length (``costs_ouro.prefill_flops``:
per pass the projections, MLPs and every (query, visible key) pair; the
head for the last position) over the bf16 peak.  The lengths and the
passes are the program's own: the ``length`` and ``passes`` stats of the
traced ``zoo/decode/admit`` spans.  Only admissions whose program ran
wholly inside the traced window count; padding to the bucket is work the
count leaves out, so it reads low here."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_admit")


def read(ctx):
    from benchmark import costs_ouro as costs
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    if spans is None:
        return None
    admits = [e[4] for e in spans.named("decode/admit")
              if "length" in e[4] and "passes" in e[4]]
    whole = [hi - lo for _, name, lo, hi, _ in spans.modules
             if PROGRAMS.match(name) and lo > spans.lo and hi < spans.hi]
    if not admits or not whole:
        return None
    flops = sum(costs.prefill_flops(
        {**ctx["config"], "total_ut_steps": a["passes"]}, a["length"])
        for a in admits) / len(admits)
    floor_s = flops / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * len(whole) * floor_s / (sum(whole) / 1e9)
