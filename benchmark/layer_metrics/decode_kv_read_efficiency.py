"""Decode model step: of the positions of a layer's key/value slab that
the traced window's decode steps READ for their live slots, the share
that was LIVE: the sum of ``kv_positions_live`` over the sum of
``kv_positions_read`` on the ``zoo/decode/dispatch`` spans (the
dispatcher knows every live slot's length; read is that length rounded
up to what a step fetches: the decode kernel's key block, or the whole
slab where the kernel does not run).  A program whose spans carry no
such stats has nothing to read."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "program_span",
                              "serve_tok_s")


def read(ctx):
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    live = read_ = 0
    for e in (spans.named("decode/dispatch") if spans else ()):
        live += e[4].get("kv_positions_live", 0)
        read_ += e[4].get("kv_positions_read", 0)
    if not read_:
        return None
    return 100.0 * live / read_
