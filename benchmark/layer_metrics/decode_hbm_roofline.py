"""Decode model step: the HBM bytes a step NEEDS (every matmul weight
once + the keys and values of the live positions of the live slots, at
the cache's declared dtype: ``costs.lm_decode_bytes_per_step``) over the
HBM peak, against the MEASURED device time a step, which is the device
time of the step programs in the trace (``XLA Modules`` events named
``jit_step`` / ``jit_stepk``, PROGRAMS below) over the steps the engine
counted while the trace ran.  Counts the work, not the implementation:
today's step reads the whole max_len slab, so this reads low, and it
reads the same work after that is repaired."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_step")


def read(ctx):
    from benchmark import costs
    c, t = ctx["counters"], ctx["trace"]
    if not t or not c.get("traced_steps"):
        return None
    seconds = sum(s for name, s in t["programs"].items()
                  if PROGRAMS.match(name))
    if not seconds:
        return None
    slots = c["traced_tokens"] / c["traced_steps"]     # live slots a step
    need = costs.lm_decode_bytes_per_step(
        ctx["config"], slots * c["mean_live_positions"])
    floor_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / c["traced_steps"])
