"""Decode model step: device time of the operations under the scope
``zoo_decode_attention`` (a layer's attention sublayer in
``generation._decode_step``: the layer norm, the q/k/v/o projections and
the decode-attention op between them, which on the chip is the kernel
``zoo_decode_attn``) over the device's busy time, each operation's time
less what runs nested in it."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")
SCOPE = "zoo_decode_attention"


def read(ctx):
    from benchmark import program_spans
    spans, t = program_spans.of_run(ctx), ctx["trace"]
    if spans is None or not t or not t["busy_s"]:
        return None
    seconds = spans.scope_seconds(SCOPE)
    if not seconds:
        return None
    return 100.0 * seconds / (t["busy_s"] * t["devices"])
