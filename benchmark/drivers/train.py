"""Driver of the training cells: the window is ONE ``Trainer.fit`` (the
loop under the Keras-style ``fit``) whose ``end_trigger`` fires when the
clock passes ``--seconds``.

Set-up builds one object, the compiled model with its state, gives it
the seed's weights, and drives it through its first three optimizer
steps by that same ``fit`` and feed, on rows that all differ; the same
object then runs the window.  After the window, with the peak memory
read and the program's state freed, the plain reference follows those
three steps and the check compares (``check.train_numbers``)."""

import gc
import importlib
import os
import time

import numpy as np

CHECK_STEPS = 3


class StopAfter:
    """end_trigger: fire after ``n`` steps of this fit."""

    def __init__(self, n):
        self.left = n

    def __call__(self, record):
        if "loss" in record:
            self.left -= 1
        return self.left <= 0


class ClockStop:
    """end_trigger: fire once ``seconds`` have passed since ``start``.
    It waits for the loss of the step BEFORE the one just enqueued, so
    the host never runs more than one step ahead of the device and the
    window closes within a step of the clock."""

    def __init__(self, seconds, tracer=None):
        self.seconds, self.tracer = seconds, tracer
        self.prev = None
        self.t0 = time.perf_counter()
        self.ends = []      # when each step but the last was seen to end

    def __call__(self, record):
        import jax
        if "loss" not in record:
            return False
        if self.prev is not None:
            jax.block_until_ready(self.prev)
            self.ends.append(time.perf_counter() - self.t0)
        self.prev = record["loss"]
        elapsed = time.perf_counter() - self.t0
        if self.tracer is not None:
            self.tracer.maybe_start(elapsed)
        return elapsed >= self.seconds


def _adam_mu(opt_state):
    """The first-moment tree inside the optimizer's state."""
    import jax
    found = [n for n in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu")) if hasattr(n, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} Adam states in the optimizer")
    return found[0].mu


def build(ctx):
    """Compile the program's model and give it the seed's weights."""
    import jax.numpy as jnp
    cfg, traffic = ctx["config"], ctx["workload"]["traffic"]
    adapter = importlib.import_module(
        "benchmark.adapters." + ctx["workload"]["adapter"])
    ref = importlib.import_module(
        "benchmark.reference." + ctx["workload"]["adapter"])
    os.environ["ZOO_TRAIN_ACCUM"] = str(traffic.get("accum_steps", 1))
    model = adapter.build(cfg, traffic)
    opt = cfg["train"]
    model.compile(opt["optimizer"], opt["loss"], seed=0,
                  compute_dtype=getattr(jnp, opt["compute_dtype"]))
    params = ref.make_params(cfg, ctx["seed"])
    model.trainer.adopt_weights(params)
    return model, adapter, ref


def first_steps(model, ref, cfg, seed, feed):
    """Steps 1..CHECK_STEPS through ``fit``; the program's side of the
    check.  The first gradient is read from Adam's first moment after
    one step: mu = (1 - b1) g."""
    import jax
    from benchmark.reference import common
    tr = model.trainer
    b1 = cfg["train"]["b1"]

    def fit(lo, hi):
        return tr.fit(feed.dataset(lo, hi - lo), feed.batch,
                      end_trigger=StopAfter(hi - lo), shuffle=False)["loss"]

    losses = fit(0, 1)
    grad_norm = {k: float(v) / (1.0 - b1) for k, v in jax.device_get(
        common.leaf_norms(_adam_mu(tr.state.opt_state))).items()}
    losses += fit(1, CHECK_STEPS)
    dparam = jax.device_get(common.diff_norms(
        tr.state.params, ref.make_params(cfg, seed)))
    return {"loss": [float(v) for v in losses], "grad_norm": grad_norm,
            "dparam_norm": {k: float(v) for k, v in dparam.items()}}


def run(ctx):
    import jax
    from benchmark import check
    cfg, spec = ctx["config"], ctx["workload"]
    traffic, say = spec["traffic"], ctx["say"]
    batch = traffic["batch"]
    model, adapter, ref = build(ctx)
    feed = adapter.Feed(traffic, cfg, ctx["seed"])
    prof = (model.trainer.enable_step_profiler()
            if ctx["tracer"] is not None else None)
    t_built = time.perf_counter()
    prog = first_steps(model, ref, cfg, ctx["seed"], feed)
    say(f"train: built in {t_built - ctx['t_start']:.1f} s, first "
        f"{CHECK_STEPS} steps in {time.perf_counter() - t_built:.1f} s; "
        f"losses {prog['loss']}")

    # ------------------------------------------------------- the window
    window_ds = feed.dataset(CHECK_STEPS)
    c0 = ctx["compiles"].snapshot()["compiles"]
    stop = ClockStop(ctx["seconds"], ctx["tracer"])
    setup_s = stop.t0 - ctx["t_start"]
    with jax.profiler.TraceAnnotation("bench/fit"):
        losses = model.trainer.fit(window_ds, batch, end_trigger=stop,
                                   shuffle=False)["loss"]
    window_s = time.perf_counter() - stop.t0
    if ctx["tracer"] is not None:
        ctx["tracer"].stop()
    compiles = ctx["compiles"].snapshot()["compiles"] - c0
    steps = len(losses)
    gaps = sorted(((b - a, i + 2) for i, (a, b) in enumerate(
        zip(stop.ends, stop.ends[1:]))), reverse=True)[:3]
    say(f"train: set-up {setup_s:.2f} s; window {window_s:.3f} s, "
        f"{steps} steps; first step seen to end at "
        f"{stop.ends[0] if stop.ends else float('nan'):.3f} s, longest "
        "intervals between step ends (s, step): "
        + ", ".join(f"{g:.3f} @ {i}" for g, i in gaps))
    counters = {"steps": steps, "batch": batch, "window_s": window_s,
                "flops_per_sample": adapter.train_flops_per_sample(
                    cfg, traffic)}
    if prof is not None:
        tl = prof.timeline()[-steps:]
        counters["data_wait_s"] = sum(e["data_wait_ms"] for e in tl) / 1e3
        counters["h2d_s"] = sum(e["h2d_ms"] for e in tl) / 1e3
    device = ctx["device_info"]()

    # ---------------------------------------- free, then the reference
    model.trainer.state = None
    del model, window_ds, stop
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    t0 = time.perf_counter()
    xs, ys = feed.reference(CHECK_STEPS)
    want = ref.train_steps(cfg, ctx["seed"], xs, ys, steps=CHECK_STEPS,
                           rows=spec["check"].get("reference_rows"))
    numbers, leaves = check.train_numbers(prog, want)
    say(f"train: reference took {time.perf_counter() - t0:.1f} s beside "
        f"{live / 1e9:.2f} GB still live; losses {want['loss']}; "
        f"worst leaves {leaves}")
    return {"attempted": steps,
            "failed": int(sum(not np.isfinite(v) for v in losses)),
            "end_to_end": {"train_samples_s": steps * batch / window_s,
                           "setup_s": setup_s},
            "counters": counters, "device": device,
            "compiles_in_window": compiles,
            "check": check.with_limits(numbers, spec["check"]["limits"])}
