"""Decode engine: of the cached positions that full-length slabs would
have made the traced decode steps fetch for their live slots, the share
that the windowed layers' rings did not hold and so nobody read: the sum
of ``kv_positions_window_skipped`` over (that + the sum of
``kv_positions_read``) on the ``zoo/decode/dispatch`` spans.  A program
whose spans carry no such stat has nothing to read."""

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "%", "program_span",
                              "serve_tok_s")


def read(ctx):
    from benchmark import program_spans, routed_spans
    spans = program_spans.of_run(ctx)
    skipped, n = routed_spans.stat_sum(spans, "decode/dispatch",
                                       "kv_positions_window_skipped")
    read_, _ = routed_spans.stat_sum(spans, "decode/dispatch",
                                     "kv_positions_read")
    if not n or not skipped + read_:
        return None
    return 100.0 * skipped / (skipped + read_)
