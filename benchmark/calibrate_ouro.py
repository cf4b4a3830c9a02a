"""Readings behind the limit of the looped decode cell's ``check``
(``ouro26-chat-closed-12``), made on the chip at the cell's own size
(the benchmark's own runs never run this):

    python3 benchmark/calibrate_ouro.py --workload <cell> \\
        --seeds 1 2 3 --controls 2

Per seed, in one process, ``drivers/decode.py::run`` as a run makes it
(a shorter window), and on the first ``--controls`` seeds the CONTROL on
the same served sample: the reference in bfloat16 all the way, the
nearest precision below the configuration's.  Then, one seed each, the
program with a PLANTED FAULT, deployed and served anew:

  fault_shared_cache   every pass of a decode step reads and writes the
                       FIRST pass's part of each layer's cache (a cache
                       shared across passes, the variant the model's
                       authors describe as a memory saving: other
                       mathematics)
  fault_norm_once      the final norm closes the last pass alone: the
                       passes before it feed the next one their output
                       un-normed

The driver reads the check 4 rows at a time here (``ROWS``): its
``logit_gaps`` is called through a wrapper put in its place for the run
(the driver is not this file's to edit), so that the program's and the
control's logits fit side by side beside the weights.  Every reading
goes through ``check.with_limits`` with the cell's own limits, as a
run's does: ``correct`` says whether it would have passed.  One JSON
line a reading."""

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROWS = 4        # rows of the check a block
FAULTS = ("fault_shared_cache", "fault_norm_once")


def plant(fault):
    """Break the served program underneath (the family's functions);
    returns the undo."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.models import generation_ouro as fam
    saved = [(fam, name, getattr(fam, name))
             for name in ("decode_attention_gqa", "_close_pass", "head")]
    real_attn, real_close, real_head = (f for _, _, f in saved)
    if fault == "fault_shared_cache":
        def first_pass_only(*a, pass_index=None):
            return real_attn(*a, pass_index=jnp.int32(0))
        fam.decode_attention_gqa = first_pass_only
    elif fault == "fault_norm_once":
        fam._close_pass = lambda params, hyper, x: x
        fam.head = lambda params, hyper, hidden: real_head(
            params, hyper, real_close(params, hyper, hidden))
    else:
        raise ValueError(f"no fault {fault!r}: {FAULTS}")
    # the family's namespace holds the head the engine's admission calls
    fam.FAMILY.head = fam.head

    def undo():
        for mod, name, f in saved:
            setattr(mod, name, f)
        fam.FAMILY.head = fam.head
    return undo


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=2,
                    help="seeds (the first) that also get the control")
    ap.add_argument("--faults", nargs="*", default=list(FAULTS))
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--ramp", type=float, default=8.0,
                    help="the load's start before the window (only to "
                         "sample sooner than the cell's ramp)")
    args = ap.parse_args(argv)
    import jax
    from analytics_zoo_tpu.common.context import enable_compile_cache
    from analytics_zoo_tpu.observability import profile
    from benchmark import check, costs, run as harness
    enable_compile_cache()
    found = copy.deepcopy(harness.resolve(args.workload))
    found["workload"]["traffic"]["ramp_s"] = args.ramp
    driver = harness.load_module("drivers", found["workload"]["driver"])
    plain_gaps = driver.logit_gaps
    driver.logit_gaps = lambda *a, **k: plain_gaps(*a, **{**k,
                                                          "block": ROWS})
    limits = found["workload"]["check"]["limits"]

    def judged(numbers):
        held = check.with_limits(numbers, limits)
        return {**numbers, "check": held,
                "correct": all(v <= lim for v, lim in held.values())}

    devs = jax.devices()[:found["cell"]["chips"]]

    def one(seed, control=None):
        ctx = harness.context(found, seed, args.seconds, devs,
                              costs.peaks(devs[0].device_kind),
                              profile.install(),
                              t_start=time.perf_counter())
        if control:
            ctx["control"] = control
        return driver.run(ctx)

    for i, seed in enumerate(args.seeds):
        out = one(seed, "bf16" if i < args.controls else None)
        print(json.dumps({"seed": seed, "program": judged(out["numbers"]),
                          "control_bf16": out["control"]
                          and judged(out["control"]),
                          "failed": out["failed"],
                          "tok_s": out["end_to_end"]["serve_tok_s"],
                          "setup_s": out["end_to_end"]["setup_s"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"]}),
              flush=True)
    for j, fault in enumerate(args.faults):
        undo = plant(fault)
        try:
            out = one(args.seeds[j % len(args.seeds)])
        finally:
            undo()
        print(json.dumps({"seed": args.seeds[j % len(args.seeds)],
                          fault: judged(out["numbers"]),
                          "failed": out["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
