"""Readings behind the limit of the state-space decode cell's ``check``
(``granite4h-chat-closed-64``), made on the chip at the cell's own size
(the benchmark's own runs never run this):

    python3 benchmark/calibrate_granitehybrid.py --workload <cell> \\
        --seeds 1 2 3 4 5 6 7 8 --controls 2

Per seed, in one process, ``drivers/decode.py::run`` as a run makes it
(a shorter window), and on the first ``--controls`` seeds the CONTROL on
the same served sample: the reference in bfloat16 all the way, the
nearest precision below the configuration's.  Then, one seed each, the
program with a PLANTED FAULT, deployed and served anew:

  fault_bucket_end   an admission lays down the state at the end of the
                     prompt's BUCKET (the padding eaten) instead of at
                     its length
  fault_window_zero  an admission leaves the convolution's window zero
  fault_state_bf16   the SSM state kept in bfloat16 (rounded after the
                     prefill and after every step)

``calibrate.py``'s way does not fit this cell: the driver reads the
check 8 rows at a time, and the program's and the control's logits side
by side do not fit beside the weights at 8 x 1280 x 100352.  The driver
is not this file's to edit, so its ``logit_gaps`` is called with
``block=4`` through a wrapper put in its place for the run.  Every
reading goes through ``check.with_limits`` with the cell's own limits, as
a run's does: ``correct`` says whether it would have passed.  One JSON
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
FAULTS = ("fault_bucket_end", "fault_window_zero", "fault_state_bf16")


def plant(fault):
    """Break the served program underneath (the family's functions and
    the state update); returns the undo."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.models import generation_granitehybrid as fam
    from analytics_zoo_tpu.ops import ssm
    saved = [(fam, "prefill", fam.prefill), (fam, "insert", fam.insert),
             (ssm, "ssm_decode", ssm.ssm_decode)]
    real_prefill, real_insert, real_step = (f for _, _, f in saved)

    def rounded(state):
        return state.astype(jnp.bfloat16).astype(jnp.float32)

    if fault == "fault_bucket_end":
        fam.prefill = lambda params, hyper, prompt, cache_len, length=None: \
            real_prefill(params, hyper, prompt, cache_len)
    elif fault == "fault_window_zero":
        def insert(hyper, caches, states, slot, length):
            states = [(jnp.zeros_like(a), b) if kind == "mamba" else (a, b)
                      for kind, (a, b) in zip(fam.layer_kinds(hyper),
                                              states)]
            return real_insert(hyper, caches, states, slot, length)
        fam.insert = insert
    elif fault == "fault_state_bf16":
        def insert(hyper, caches, states, slot, length):
            states = [(a, rounded(b)) if kind == "mamba" else (a, b)
                      for kind, (a, b) in zip(fam.layer_kinds(hyper),
                                              states)]
            return real_insert(hyper, caches, states, slot, length)

        def step(state, *a):
            y, state = real_step(state, *a)
            return y, rounded(state)
        fam.insert, ssm.ssm_decode = insert, step
    # the family's namespace holds the functions the engine calls
    fam.FAMILY.prefill, fam.FAMILY.insert = fam.prefill, fam.insert

    def undo():
        for mod, name, f in saved:
            setattr(mod, name, f)
        fam.FAMILY.prefill, fam.FAMILY.insert = fam.prefill, fam.insert
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
                          "setup_s": out["end_to_end"]["setup_s"]}),
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
