"""Input layer (data/dataset.py, common/prefetch.py, native/): seconds
the fit loop waited for a batch (stepprof ``data_wait``) plus seconds
the prefetch thread spent uploading it (``h2d``, which overlaps compute
by design), over the window."""

LAYER, UNIT, SOURCE, MOVES = "Input", "%", "program_span", "train_samples_s"


def read(ctx):
    c = ctx["counters"]
    if "data_wait_s" not in c:
        return None
    return 100.0 * (c["data_wait_s"] + c["h2d_s"]) / c["window_s"]
