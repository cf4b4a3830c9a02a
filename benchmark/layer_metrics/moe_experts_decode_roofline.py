"""Kernels (ops/moe.py): the least time the chip could take for the held
experts' part of the traced decode steps over the device time of the
operations under the scope ``zoo_moe_experts`` IN THE STEP PROGRAMS
(scope paths that start ``jit(step``; an admit plan runs the same scope
over a whole prompt and is left out).  The least time of one layer's
call: max(the routed pairs' flops / the bf16 peak, the bytes of the
experts that a token hit / the HBM peak)
(``costs_cohere2moe.experts_step_floor_s``), times layers and traced
steps.  The experts hit are the program's own count where its
``zoo/decode/fanout`` spans carry it, else the expectation under
uniform routing."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace", "serve_tok_s")
SCOPE = "zoo_moe_experts"


def read(ctx):
    from benchmark import costs_cohere2moe as costs
    from benchmark import program_spans, routed_spans
    spans, c, cfg = program_spans.of_run(ctx), ctx["counters"], ctx["config"]
    if spans is None or not c.get("traced_steps"):
        return None
    seconds = routed_spans.step_programs_only(spans).scope_seconds(SCOPE)
    if not seconds:
        return None
    slots = c["traced_tokens"] / c["traced_steps"]
    hit = routed_spans.experts_hit_per_layer_step(spans, cfg)
    if hit is None:
        hit = costs.experts_hit_per_layer(cfg, slots)
    floor_s = costs.experts_step_floor_s(cfg, slots, hit, ctx["peaks"])
    return (100.0 * c["traced_steps"] * cfg["num_hidden_layers"] * floor_s
            / seconds)
