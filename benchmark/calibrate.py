"""Readings for the limits of ``correct``, made on the chip at the
cell's own size (the benchmark's own runs never run this):

    python3 benchmark/calibrate.py --workload <cell> --seeds 1 2 3

For a training cell, per seed and in one process: the program's first
steps as a run makes them; the plain reference; and for the first few
seeds the CONTROL, the reference in the nearest precision below the
configuration's (fp8 operands for a bfloat16 configuration), put in the
program's place, and the FAULT "half of the batch left out, the mean
taken over the rest", planted in the reference put in the program's
place.  Each is compared with the reference exactly as a run compares
the program; ``--dump`` keeps every reading leaf by leaf.  (A state left
unchanged reads 1 by the measure and needs no run.)

For a decode cell see ``drivers/decode.py::calibrate``."""

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL_MODE = {"bfloat16": "fp8", "float32": "bf16"}


def train(found, seeds, controls=4, dump=None):
    """Per seed, in ONE process: the program's first steps (the driver's
    own ``build`` and ``first_steps``; no window), then, with its state
    freed, the reference; for the first ``controls`` seeds also the
    control and the fault.  ``dump``: a file that gets every reading
    leaf by leaf, one JSON line each, so that a limit can be set from
    any statistic of them without another chip call."""
    import gc
    import jax
    from benchmark import check
    driver = importlib.import_module("benchmark.drivers.train")
    cfg, spec = found["config"], found["workload"]
    traffic = spec["traffic"]
    batch, rows = traffic["batch"], spec["check"].get("reference_rows")
    control = CONTROL_MODE[cfg["train"]["compute_dtype"]]
    steps = driver.CHECK_STEPS
    model, adapter, ref = driver.build({**found, "seed": seeds[0]})
    sink = open(dump, "w") if dump else None
    for i, seed in enumerate(seeds):
        feed = adapter.Feed(traffic, cfg, seed)
        if i:
            model.trainer.adopt_weights(ref.make_params(cfg, seed))
        readings = {"program": driver.first_steps(model, ref, cfg, seed,
                                                  feed)}
        model.trainer.state = None
        gc.collect()
        xs, ys = feed.reference(steps)
        want = ref.train_steps(cfg, seed, xs, ys, steps, rows)
        readings["reference"] = want
        if i < controls:
            readings["control_" + control] = ref.train_steps(
                cfg, seed, xs, ys, steps, rows, mode=control)
            readings["fault_half_batch"] = ref.train_steps(
                cfg, seed, xs, ys, steps, rows, batch_rows=batch // 2)
        for name, got in readings.items():
            if sink:
                sink.write(json.dumps({"seed": seed, "what": name, **got})
                           + "\n")
                sink.flush()
            if name != "reference":
                numbers, leaves = check.train_numbers(got, want)
                print(json.dumps({"seed": seed, "what": name, **numbers,
                                  "leaves": leaves}), flush=True)
    live = sum(a.nbytes for a in jax.live_arrays())
    print(json.dumps({"live_bytes_at_end": live}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dump", help="a file for every reading, leaf by leaf")
    args = ap.parse_args(argv)
    from benchmark import run as harness
    found = harness.resolve(args.workload)
    import jax
    from analytics_zoo_tpu.common.context import enable_compile_cache
    enable_compile_cache()
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "workload": args.workload}), flush=True)
    driver = harness.load_module("drivers", found["workload"]["driver"])
    if hasattr(driver, "calibrate"):
        return driver.calibrate(found, args.seeds)
    return train(found, args.seeds, dump=args.dump)


if __name__ == "__main__":
    sys.exit(main())
