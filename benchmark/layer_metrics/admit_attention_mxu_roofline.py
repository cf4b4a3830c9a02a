"""Decode model step: the least time the chip could take for the causal
attention over the traced window's admissions (``admit_parts.
attention_flops``: 4 h hd per visible (query, key) pair of each
attention layer, inside its window where it has one, at each prompt's
own length) over the bf16 peak, against the device time of the part
``zoo_attn_core`` of the admit programs that ran wholly inside the trace
(on the chip the kernel ``zoo_flash_fwd``).  Padding to the bucket and
masked tiles are work the count leaves out, so they read low here."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "device_trace",
                              "serve_tok_s")


def read(ctx):
    from benchmark import admit_parts
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return admit_parts.roofline(
        ctx, (admit_parts.ATTN_CORE,),
        lambda n: admit_parts.attention_flops(ctx, n) / peak)
