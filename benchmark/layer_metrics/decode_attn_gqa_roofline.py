"""Kernels (ops/attention.py): the least time the chip could take for
the decode step's grouped-query attention over the device time of the
``XLA Ops`` events named ``zoo_decode_attn_gqa``, the ``name=`` of its
``pallas_call``.  A call (one layer, all slots) needs its live rows of
keys and of values once: rows x 2 x kv_heads x d_head x 2 bytes over the
HBM peak (bytes bind it: 16 queries a cached head do not fill the MXU's
time).  The live rows a call are the program's own count: the traced
``zoo/decode/dispatch`` spans' ``kv_positions_live`` (summed over the
layers and the steps of a dispatch) over their steps x layers."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace", "serve_tok_s")
KERNEL = "zoo_decode_attn_gqa"


def read(ctx):
    from benchmark import costs_cohere2moe as costs
    from benchmark import program_spans
    spans, cfg = program_spans.of_run(ctx), ctx["config"]
    if spans is None:
        return None
    seconds, calls = spans.kernel_seconds(KERNEL)
    rows = steps = 0
    for e in spans.named("decode/dispatch"):
        rows += e[4].get("kv_positions_live", 0)
        steps += e[4].get("k", 0)
    if not seconds or not rows or not steps:
        return None
    per_call = rows / (steps * cfg["num_hidden_layers"])
    floor_s = (costs.decode_attn_bytes_per_call(cfg, per_call)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * floor_s / seconds
