"""Decode model step (models/generation_cohere2moe.py): the flops one
output token REQUIRES (``costs_cohere2moe.decode_flops_per_token`` at the
window's mean live positions a slot: projections, router, shared
experts, the held experts a token is routed to, the cached rows inside
each layer's window, the head) x tokens/s delivered, over the bf16 peak.
Small by nature (decode is bound by memory); it is the whole step's
share of the chip, which bounds every kernel's claim.  The prefills'
flops are not counted: they are not the step's."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "host_clock",
                              "serve_tok_s")


def read(ctx):
    from benchmark import costs_cohere2moe as costs
    c = ctx["counters"]
    if not c.get("tokens"):
        return None
    flops = costs.decode_flops_per_token(ctx["config"],
                                         c["mean_live_positions"])
    return (100.0 * flops * c["tokens"] / c["window_s"]
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
