"""Device: 1 - (union of the intervals in which an operation ran on the
device) / (traced window), from the profiler's trace, averaged over the
chips."""

LAYER, UNIT, SOURCE, MOVES = ("Device", "%", "device_trace",
                              "serve_tok_s")


def read(ctx):
    from benchmark import xplane
    return xplane.idle_share(ctx["trace"])
