"""Kernels (ops/attention.py): the least time the chip could take for
the flash kernels' work over the device time they took.  Device time:
every pallas call of the train step in the trace (``XLA Ops`` events
whose HLO text holds custom_call_target="tpu_custom_call"; the three
kernels, forward, dq and dkv, carry no name of their own today, so each
(layer, microbatch) shows as three calls).  Least time: per such triple,
max(``costs.flash_flops`` / bf16 peak, ``costs.flash_bytes`` / HBM
peak) at the microbatch's shape, the causal half counted once and the
backward's recomputed scores not counted."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace",
                              "train_samples_s")


def read(ctx):
    from benchmark import costs
    t, cfg = ctx["trace"], ctx["config"]
    traffic = ctx["workload"]["traffic"]
    if not t or not t.get("pallas_calls"):
        return None
    seconds = sum(s for s, _ in t["pallas_calls"].values())
    calls = sum(n for _, n in t["pallas_calls"].values())
    micro = traffic["batch"] // traffic.get("accum_steps", 1)
    seq, d = traffic["seq_len"], cfg["n_embd"]
    floor_s = max(costs.flash_flops(micro, seq, d)
                  / ctx["peaks"]["bf16_flops_per_s"],
                  costs.flash_bytes(micro, seq, d)
                  / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * (calls / 3.0) * floor_s / seconds
