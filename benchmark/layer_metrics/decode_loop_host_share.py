"""Decode engine: the share of the traced window in which the
dispatcher thread did host work: its self time in ``zoo/decode/admit``,
``dispatch`` and ``fanout`` and whatever lies outside any span, that is
everything but waiting on the device (``fetch``, ``admit_fetch``) or for
work (``idle``)."""

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "%", "program_span",
                              "serve_tok_s")
WAITING = ("decode/fetch", "decode/admit_fetch", "decode/idle")


def read(ctx):
    import sys
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    thread = spans.thread_of("decode/") if spans else None
    if thread is None or not spans.window_s:
        return None
    own = spans.self_seconds(thread)
    waiting = sum(own.get(program_spans.span(n), 0.0) for n in WAITING)
    print("decode_loop_host_share: dispatcher self seconds "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(own.items()))
          + f" of {spans.window_s:.4f}", file=sys.stderr, flush=True)
    return 100.0 * (spans.window_s - waiting) / spans.window_s
