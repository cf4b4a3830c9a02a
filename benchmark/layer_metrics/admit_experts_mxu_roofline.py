"""Decode model step: the least time the chip could take for the held
experts of the traced window's admissions (``admit_parts.
experts_floor_s``: the (token, held expert) pairs of every layer at
each prompt's own length, ``held_pairs_per_token`` a token for uniform
routing, over the bf16 peak, or the held experts' weights read once
over the HBM peak, whichever is longer) against the device time of the
part ``zoo_moe_experts`` of the admit programs that ran wholly inside
the trace.  Padding to the bucket reads low here."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")


def read(ctx):
    from benchmark import admit_parts
    return admit_parts.roofline(
        ctx, (admit_parts.EXPERTS,),
        lambda n: admit_parts.experts_floor_s(ctx, n, ctx["peaks"]))
