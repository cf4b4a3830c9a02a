"""Decode model step: the device time of the admit programs (``XLA
Modules`` events ``^jit_admit`` that ran wholly inside the traced
window) that lies under none of the program's part names
(``profile.ADMIT_PARTS``), over those programs' device time: what
``benchmark/admit_parts.py``'s split cannot place.  Each operation's
time counts less what runs nested in it."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")


def read(ctx):
    from benchmark import admit_parts
    s = admit_parts.split(ctx)
    if s is None:
        return None
    return s.share(admit_parts.UNNAMED)
