"""Decode model step: the HBM bytes a step NEEDS (the non-expert weights
once, the shared experts, the held experts that a token HIT, as the
program counted them on its ``zoo/decode/fanout`` spans, else in
expectation, and the keys and values inside each layer's window of the
live slots, all at 2 bytes: ``costs_cohere2moe.decode_bytes_per_step``)
over the HBM peak, against the MEASURED device time a step: the device
time of the step programs in the trace (``XLA Modules`` events
``^jit_step``) over the steps the engine counted while the trace ran.
Counts the work, not the implementation: a step that reads every held
expert, or rows outside the window, reads low here."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_step")


def read(ctx):
    from benchmark import costs_cohere2moe as costs
    from benchmark import program_spans, routed_spans
    c, t = ctx["counters"], ctx["trace"]
    if not t or not c.get("traced_steps"):
        return None
    seconds = sum(s for name, s in t["programs"].items()
                  if PROGRAMS.match(name))
    if not seconds:
        return None
    slots = c["traced_tokens"] / c["traced_steps"]     # live slots a step
    hit = routed_spans.experts_hit_per_layer_step(
        program_spans.of_run(ctx), ctx["config"])
    need = costs.decode_bytes_per_step(ctx["config"], slots,
                                       c["mean_live_positions"], hit)
    floor_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / c["traced_steps"])
