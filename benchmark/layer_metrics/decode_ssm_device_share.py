"""Decode model step: device time of the operations under the scope
``zoo_ssm`` (a Mamba-2 mixer of ``ops/ssm.py``, in_proj to out_proj,
with its convolution and its scan or state update; in step and admit
plans alike) over the device's busy time, each operation's time less
what runs nested in it.  Says whether the state-space layers hold the
device."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
SCOPE = "zoo_ssm"


def read(ctx):
    from benchmark import program_spans
    spans, t = program_spans.of_run(ctx), ctx["trace"]
    if spans is None or not t or not t["busy_s"]:
        return None
    seconds = spans.scope_seconds(SCOPE)
    if not seconds:
        return None
    return 100.0 * seconds / (t["busy_s"] * t["devices"])
