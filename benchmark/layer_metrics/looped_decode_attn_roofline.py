"""Kernels (ops/attention.py), in a looped model: the least time the
chip could take for the decode step's grouped-query attention over the
device time of the ``XLA Ops`` events named ``zoo_decode_attn_gqa``, the
``name=`` of its ``pallas_call``.  A call (one layer, one pass, all
slots) needs its live rows of keys and of values of that pass once: rows
x 2 x kv_heads x d_head x 2 bytes over the HBM peak (bytes bind it).
The live rows a call are the program's own count: the traced
``zoo/decode/dispatch`` spans' ``kv_positions_live`` (summed over every
pass of every layer and the steps of a dispatch) over their steps x
``passes`` x layers, the passes from the same spans."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace", "serve_tok_s")
KERNEL = "zoo_decode_attn_gqa"


def read(ctx):
    from benchmark import costs_ouro as costs
    from benchmark import program_spans
    spans, cfg = program_spans.of_run(ctx), ctx["config"]
    if spans is None:
        return None
    seconds, calls = spans.kernel_seconds(KERNEL)
    rows = slabs = 0
    for e in spans.named("decode/dispatch"):
        if "passes" in e[4]:
            rows += e[4].get("kv_positions_live", 0)
            slabs += e[4].get("k", 0) * e[4]["passes"]
    if not seconds or not rows or not slabs:
        return None
    per_call = rows / (slabs * cfg["num_hidden_layers"])
    floor_s = (costs.decode_attn_bytes_per_call(cfg, per_call)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * floor_s / seconds
