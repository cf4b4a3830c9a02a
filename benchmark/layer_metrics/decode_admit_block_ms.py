"""Decode engine: how long an admission holds the dispatcher's loop:
the median duration of the ``zoo/decode/admit`` spans of the traced
window, their ``admit_fetch`` child (the blocking fetch of the first
token, behind the window in flight) included."""

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "ms", "program_span",
                              "serve_ttft_p99_ms")


def read(ctx):
    import statistics
    import sys
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    # a span cut by the window's edge is not a whole admission
    admits = [e for e in (spans.named("decode/admit") if spans else ())
              if e[2] > spans.lo and e[3] < spans.hi]
    if not admits:
        return None
    whole = [(e[3] - e[2]) / 1e6 for e in admits]
    fetch = [(e[3] - e[2]) / 1e6 for e in spans.named("decode/admit_fetch")]
    # request by request, wait + block is the time to first token as
    # the engine sees it; the two medians add up only where one mode of
    # the wait holds the sample
    first = [e[4].get("queue_wait_us", 0) / 1e3 + (e[3] - e[2]) / 1e6
             for e in admits]
    print(f"decode_admit_block_ms: {len(whole)} admissions, median "
          f"{statistics.median(whole):.2f} ms, of which the fetch "
          f"{statistics.median(fetch) if fetch else float('nan'):.2f} ms; "
          f"queue wait + block, request by request: median "
          f"{statistics.median(first):.2f} ms", file=sys.stderr, flush=True)
    return statistics.median(whole)
