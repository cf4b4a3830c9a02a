"""Decode engine (pipeline/inference/decode.py): tokens the engine
produced over (steps it ran x its slots), from ``DecodeEngine.stats()``
at the window's two ends."""

LAYER, UNIT, SOURCE, MOVES = ("Decode engine", "%", "program_counter",
                              "serve_tok_s")


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return 100.0 * c["engine_tokens"] / (c["steps"] * c["capacity"])
