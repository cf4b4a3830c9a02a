"""Decode engine (pipeline/inference/decode.py): how long a request
waited between ``submit()`` and its admission into a slot: the median
``queue_wait_us`` over the ``zoo/decode/admit`` spans of the traced
window (the engine takes the time at both ends itself; the span carries
it as a stat).  With ``decode_admit_block_ms`` it is the inside of the
clients' time to first token."""

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "ms", "program_span",
                              "serve_ttft_p99_ms")


def read(ctx):
    import statistics
    import sys
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    waits = [e[4]["queue_wait_us"] / 1e3
             for e in (spans.named("decode/admit") if spans else ())
             if "queue_wait_us" in e[4]]
    if not waits:
        return None
    # two modes: a request that the loop finds on its next round, and
    # one that just missed it and waits out a fused window
    print(f"decode_queue_wait_ms: {len(waits)} admissions, median "
          f"{statistics.median(waits):.2f} ms, longest {max(waits):.2f} "
          f"ms, {sum(w > 20.0 for w in waits)} of them over 20 ms",
          file=sys.stderr, flush=True)
    return statistics.median(waits)
