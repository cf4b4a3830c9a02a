"""Decode model step: the least time the chip could take for the dense
matmuls of the traced window's admissions (``admit_parts.dense_flops``:
2 x each prompt's own length x the weights a token multiplies by in the
q/k/v/o projections, MLPs, router, shared experts and a Mamba mixer's
in and out projections, and the head at the last position alone) over
the bf16 peak, against the device time of the parts ``zoo_attn_proj``,
``zoo_mlp``, ``zoo_moe_shared``, ``zoo_moe_router``, ``zoo_ssm_proj``
and ``zoo_head`` of the admit programs that ran wholly inside the trace.
Padding to the bucket reads low here."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")


def read(ctx):
    from benchmark import admit_parts
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return admit_parts.roofline(
        ctx, admit_parts.DENSE,
        lambda n: admit_parts.dense_flops(ctx, n) / peak)
