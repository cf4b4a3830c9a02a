"""Device: the share of the device's idle seconds in the traced window
that lie under a ``zoo/`` span of the decode dispatcher's thread, each
idle moment given to the most specific span open then; the idle seconds
by span name go to stderr.  It says how much of the idle time the
program can name, not how much there is (``serve_device_idle_share``)."""

LAYER, UNIT, SOURCE, MOVES = ("Device", "%", "device_trace", "serve_tok_s")


def read(ctx):
    import json
    import sys
    from benchmark import program_spans
    spans = program_spans.of_run(ctx)
    thread = spans.thread_of("decode/") if spans else None
    if thread is None or not spans.ops:
        return None
    idle = spans.idle_by_span(thread)
    total = sum(idle.values())
    if not total:
        return None
    print("idle seconds by span: " + json.dumps(
        sorted(idle.items(), key=lambda kv: -kv[1])), file=sys.stderr,
        flush=True)
    return 100.0 * (total - idle.get("unattributed", 0.0)) / total
