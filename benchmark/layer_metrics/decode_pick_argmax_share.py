"""Decode model step: of the decode steps the traced window dispatched,
the share that picked their tokens by argmax alone: the sum of ``k``
over the ``zoo/decode/dispatch`` spans whose stat ``pick_sorted`` is 0
over the sum of ``k`` of all that carry the stat.  ``pick_sorted`` is
the flag the dispatcher hands the step plan: 1 when a live slot samples,
and the step then sorts the vocabulary for every slot.  A program whose
spans carry no such stat has nothing to read."""

LAYER, UNIT, SOURCE, MOVES = ("Decode model step", "%", "program_span",
                              "serve_tok_s")


def read(ctx):
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    steps = by_argmax = 0
    for e in (spans.named("decode/dispatch") if spans else ()):
        if "pick_sorted" in e[4]:
            steps += e[4]["k"]
            by_argmax += 0 if e[4]["pick_sorted"] else e[4]["k"]
    if not steps:
        return None
    return 100.0 * by_argmax / steps
