"""Readings behind the limits of a routed decode cell's ``check``
(``cmdaplus-docqa-closed-5k``: ``off_best_share``), made on the chip at
the cell's own size (the benchmark's own runs never run this):

    python3 benchmark/calibrate_routed.py --workload <cell> \\
        --seeds 1 2 3 4 5 6 7 8 --controls 3

``calibrate.py``'s way (``drivers/decode.py::calibrate``), which this
cell's size does not let it go: that function takes the check's rows 8
at a time, and the program's, the control's and a fault's logits side by
side do not fit beside the weights at 8 x 6144 x 32768.  The driver is
not this file's to edit, so its ``logit_gaps`` is called with ``block=4``
through a wrapper put in its place for the run, and ``sample_finished``
through one that keeps the sample, so that control and faults are read
on the SAME served requests as the program:

  control_bf16   the reference in the nearest precision below the
                 configuration's (bfloat16 all the way)
  fault_shared3  one of the four shared experts dropped (the mean over
                 three)
  fault_w4095    a window of 4095

Every reading goes through ``check.with_limits`` with the cell's own
limits, as a run's does: ``correct`` says whether it would have passed.
The control has to come out as NOT correct on every seed.

One short window a seed, all in one process; one JSON line a seed."""

import argparse
import copy
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = {"fault_shared3": {"num_shared_experts": 3},
          "fault_w4095": {"sliding_window": 4095}}
ROWS = 4        # rows of the check a block


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="seeds (the first) that also get control and "
                         "faults")
    ap.add_argument("--faults", type=int, default=1,
                    help="0: the control alone, no planted fault")
    ap.add_argument("--seconds", type=float, default=14.0)
    args = ap.parse_args(argv)
    import jax
    from analytics_zoo_tpu.common.context import enable_compile_cache
    from analytics_zoo_tpu.observability import profile
    from benchmark import check, costs, run as harness
    enable_compile_cache()
    found = copy.deepcopy(harness.resolve(args.workload))
    found["workload"]["traffic"]["ramp_s"] = 12.0   # only to sample sooner
    driver = harness.load_module("drivers", found["workload"]["driver"])
    ref = importlib.import_module(
        "benchmark.reference." + found["workload"]["adapter"])
    plain_gaps, plain_logits, plain_sample = (
        driver.logit_gaps, ref.logits_fn, driver.sample_finished)
    kept = {}

    def sample_and_keep(*a, **k):
        kept["sample"] = plain_sample(*a, **k)
        return kept["sample"]

    def logits_fn(p, x, cfg, mode="f32"):
        if mode in FAULTS:
            return plain_logits(p, x, {**cfg, **FAULTS[mode]}, "f32")
        return plain_logits(p, x, cfg, mode)

    driver.logit_gaps = lambda *a, **k: plain_gaps(*a, **{**k,
                                                          "block": ROWS})
    driver.sample_finished = sample_and_keep
    ref.logits_fn = logits_fn
    limits = found["workload"]["check"]["limits"]

    def judged(numbers):
        """The numbers beside the cell's limits, as ``run.py`` judges a
        run's: ``correct`` when every one is within its limit."""
        held = check.with_limits(numbers, limits)
        return {**numbers, "check": held,
                "correct": all(v <= lim for v, lim in held.values())}

    devs = jax.devices()[:found["cell"]["chips"]]
    for i, seed in enumerate(args.seeds):
        ctx = harness.context(found, seed, args.seconds, devs,
                              costs.peaks(devs[0].device_kind),
                              profile.install(),
                              t_start=time.perf_counter())
        if i < args.controls:
            ctx["control"] = "bf16"
        out = driver.run(ctx)
        line = {"seed": seed, "program": judged(out["numbers"]),
                "control_bf16": out["control"] and judged(out["control"]),
                "failed": out["failed"],
                "tok_s": out["end_to_end"]["serve_tok_s"]}
        if i < args.controls and args.faults:
            for fault in FAULTS:
                line[fault] = judged(driver.gap_numbers(driver.logit_gaps(
                    ref, found["config"], seed, kept["sample"],
                    control=fault)))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
