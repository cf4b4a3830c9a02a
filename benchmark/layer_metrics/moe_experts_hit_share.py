"""Decode model step: of the experts held here, the share that at least
one live slot's token was routed to, a layer and a step: the program's
counter ``moe_experts_hit`` (its increments ride the traced
``zoo/decode/fanout`` spans, beside the ``steps`` they cover) over held
experts x layers x steps.  It says how many of the held experts' bytes a
step cannot avoid; 95.5 % is the expectation at 48 slots, 8 of 128."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "program_counter",
                              "serve_tok_s")


def read(ctx):
    from benchmark import program_spans, routed_spans
    cfg = ctx["config"]
    hit = routed_spans.experts_hit_per_layer_step(
        program_spans.of_run(ctx), cfg)
    if hit is None:
        return None
    return 100.0 * hit / cfg["experts_held"][1]
