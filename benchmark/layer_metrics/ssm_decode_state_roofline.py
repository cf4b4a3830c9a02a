"""Kernels (ops/ssm.py): the least time the chip could take for the
decode step's state update over the device time of the ``XLA Ops``
events named ``zoo_ssm_decode``, the ``name=`` of its ``pallas_call``.
A call (one layer, all slots) needs each LIVE slot's float32 state read
and written once, with its ``dt x``, ``exp(dt A)``, ``B``, ``C`` in and
``y`` out (``costs_granitehybrid.ssm_decode_bytes_per_call``), over the
HBM peak: bytes bind it.  The live slots a step are the program's own
count: the traced ``zoo/decode/dispatch`` spans' ``live``, weighted by
their steps ``k``.  A free slot's state, which the kernel updates too,
is work the count leaves out."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace", "serve_tok_s")
KERNEL = "zoo_ssm_decode"


def read(ctx):
    from benchmark import costs_granitehybrid as costs
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    if spans is None:
        return None
    seconds, calls = spans.kernel_seconds(KERNEL)
    live = steps = 0
    for e in spans.named("decode/dispatch"):
        if "live" in e[4] and "k" in e[4]:
            live += e[4]["live"] * e[4]["k"]
            steps += e[4]["k"]
    if not seconds or not steps:
        return None
    floor_s = (costs.ssm_decode_bytes_per_call(ctx["config"], live / steps)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * floor_s / seconds
