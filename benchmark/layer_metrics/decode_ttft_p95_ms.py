"""Decode engine: the 95th percentile of submit -> first token over the
window's requests, from the clients' own clocks.  First tokens come at
the ends of fused windows, so the times sit on steps one window apart
(88 ms, 177 ms at 16 slots), and the share of requests on the second
step lies near 5 %: this percentile flips between the two steps from run
to run.  That is why it is read here, without a bound, and the
end-to-end tail is the 99th (``serve_ttft_p99_ms``)."""

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "ms", "host_clock",
                              "serve_ttft_p99_ms")


def read(ctx):
    return ctx["counters"].get("ttft_p95_ms")
