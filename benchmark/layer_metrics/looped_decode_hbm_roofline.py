"""Decode model step of a looped model: the HBM bytes a step NEEDS
(``costs_ouro.decode_bytes_per_step``: every layer's weights once a pass,
the head once, each live slot's keys and values of every layer and every
pass, the new rows) over the HBM peak, against the MEASURED device time
a step: the device time of the step programs in the trace (``XLA
Modules`` events ``^jit_step``) over the steps the engine counted while
the trace ran.  The passes a step ran are the program's own: the
``passes`` stat of the traced ``zoo/decode/dispatch`` spans (a program
whose spans carry none has nothing to read).  Counts the work, not the
implementation: a step that reads a free slot's rows reads low here."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_step")


def read(ctx):
    from benchmark import costs_ouro as costs
    from benchmark import program_spans
    c, t, spans = ctx["counters"], ctx["trace"], program_spans.of_run(ctx)
    if not t or not c.get("traced_steps") or spans is None:
        return None
    seen = {e[4]["passes"] for e in spans.named("decode/dispatch")
            if "passes" in e[4]}
    passes = seen.pop() if len(seen) == 1 else None
    seconds = sum(s for name, s in t["programs"].items()
                  if PROGRAMS.match(name))
    if not passes or not seconds:
        return None
    cfg = {**ctx["config"], "total_ut_steps": passes}
    slots = c["traced_tokens"] / c["traced_steps"]     # live slots a step
    need = costs.decode_bytes_per_step(cfg, slots, c["mean_live_positions"])
    floor_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / c["traced_steps"])
