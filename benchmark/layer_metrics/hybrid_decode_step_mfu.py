"""Decode model step (models/generation_granitehybrid.py): the flops one
output token REQUIRES (``costs_granitehybrid.decode_flops_per_token`` at
the window's mean live positions a slot: every projection and MLP, the
convolution, the state update, attention over the live rows of the
attention layers, the tied head) x tokens/s delivered, over the bf16
peak: the whole step's share of the chip.  Small by nature (a decode
step is bound by memory); the prefills' flops are not counted."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "host_clock",
                              "serve_tok_s")


def read(ctx):
    from benchmark import costs_granitehybrid as costs
    c = ctx["counters"]
    if not c.get("tokens"):
        return None
    flops = costs.decode_flops_per_token(ctx["config"],
                                         c["mean_live_positions"])
    return (100.0 * flops * c["tokens"] / c["window_s"]
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
