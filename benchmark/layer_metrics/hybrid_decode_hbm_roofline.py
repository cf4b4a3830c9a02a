"""Decode model step: the HBM bytes a step NEEDS (every weight once at 2
bytes, each live slot's float32 SSM state read and written, its
convolution windows, and the live keys and values of the attention
layers: ``costs_granitehybrid.decode_bytes_per_step``) over the HBM
peak, against the MEASURED device time a step: the device time of the
step programs in the trace (``XLA Modules`` events ``^jit_step``) over
the steps the engine counted while the trace ran.  Counts the work, not
the implementation: a step that reads a free slot's state, or whole
slabs where rows are live, reads low here."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
PROGRAMS = re.compile(r"^jit_step")


def read(ctx):
    from benchmark import costs_granitehybrid as costs
    c, t = ctx["counters"], ctx["trace"]
    if not t or not c.get("traced_steps"):
        return None
    seconds = sum(s for name, s in t["programs"].items()
                  if PROGRAMS.match(name))
    if not seconds:
        return None
    slots = c["traced_tokens"] / c["traced_steps"]     # live slots a step
    need = costs.decode_bytes_per_step(ctx["config"], slots,
                                       c["mean_live_positions"])
    floor_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / c["traced_steps"])
