"""Trainer step (train/trainer.py): the flops the forward and backward
REQUIRE per sample (``costs``, from the configuration's widths;
recomputation not counted) x samples/s of this run's window, over chips
x the bf16 peak.  The whole step's share of the chip: it bounds every
kernel's claim."""

LAYER, UNIT, SOURCE, MOVES = ("Trainer step", "%", "host_clock",
                              "train_samples_s")


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    rate = c["steps"] * c["batch"] / c["window_s"]
    return (100.0 * c["flops_per_sample"] * rate
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
