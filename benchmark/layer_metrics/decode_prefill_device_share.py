"""Decode engine: device time of the admit (prefill) programs over the
device's busy time, by program name in the trace (``XLA Modules``
events named ``jit_admit`` / ``jit_padmit``, PROGRAMS below)."""

import re

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "%", "device_trace",
                              "serve_ttft_p99_ms")
PROGRAMS = re.compile(r"^jit_p?admit")


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"]:
        return None
    seconds = sum(s for name, s in t["programs"].items()
                  if PROGRAMS.match(name))
    if not seconds:
        return None
    return 100.0 * seconds / (t["busy_s"] * t["devices"])
