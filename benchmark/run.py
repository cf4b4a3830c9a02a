"""The benchmark's one entry:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child, sets no platform.  It finds the cell in
``BENCHMARK.json``, its limits in ``benchmark/workloads/<cell>.json``,
its traffic mix in ``benchmark/traffic/<traffic>.json``, its
configuration's file, the
driver ``benchmark/drivers/<driver>.py`` the workload names, and (traced
run) one reader ``benchmark/layer_metrics/<metric>.py`` for every
per-layer metric that lists the cell.  A later PR adds files and entries
and edits none.  Exits non-zero with no result line when jax finds no
TPU, fewer chips than the cell asks for, or a device kind that
``peaks.json`` lacks.  The last line of stdout is the result."""

import time

T_START = time.perf_counter()      # set-up counts from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload, manifest=None):
    """The cell's entry, its workload file (with its traffic mix's file
    under ``traffic``), its configuration and the metrics it reports,
    all found by name."""
    manifest = manifest or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    spec = load_json(HERE, "workloads", workload + ".json")
    spec["traffic"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    config = load_json(ROOT, configs[cell["config"]]["file"])

    def reported(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "workload": spec, "config": config,
            "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
            "per_layer": [m for m in manifest["per_layer"]
                          if workload in m.get("workloads", ())]}


class Tracer:
    """The profiler around the END of the window: ``maybe_start`` begins
    the trace once ``after_s`` of the window have passed, ``stop`` ends
    it after the window has closed, so that writing the trace stalls
    nothing that is measured.  The python tracer is off: the host lines
    then hold the benchmark's own ``bench/...`` annotations and little
    else."""

    def __init__(self, trace_dir, after_s):
        self.dir, self.after_s = trace_dir, after_s
        self.started = self.stopped = None
        self._span = None

    def maybe_start(self, elapsed_s):
        if self.started is not None or elapsed_s < self.after_s:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = time.perf_counter()
        self._span = jax.profiler.TraceAnnotation("bench/traced")
        self._span.__enter__()

    def stop(self):
        if self.started is None or self.stopped is not None:
            return
        import jax
        self._span.__exit__(None, None, None)
        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self):
        """The reduced trace, clipped to the ``bench/traced`` span."""
        from benchmark import xplane
        if self.stopped is None:
            return None
        rows = xplane.load(self.dir)
        span = [r for r in rows if r[2] == "bench/traced"]
        lo, hi = ((span[0][3], span[0][3] + span[0][4]) if span
                  else (None, None))
        return xplane.reduce(rows, lo, hi)


TRACE_SECONDS = 4.0     # the traced part of the window, at its end


def device_info(devs):
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def result_line(found, out, peaks, trace=None):
    """The one JSON object of the last line.  ``out`` is what the
    driver's ``run`` returned; ``trace`` the reduced trace of a
    ``--trace 1`` run (then the metrics are the cell's per-layer ones,
    each from its own reader, and a reader that finds nothing to read is
    left out)."""
    from benchmark import xplane
    check = out["check"]
    correct = bool(check) and all(
        v is not None and v == v and v <= lim for v, lim in check.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    device = dict(out["device"])
    if trace is None:
        result["metrics"] = {
            m["name"]: {"value": out["end_to_end"][m["name"]],
                        "unit": m["unit"]} for m in found["end_to_end"]}
    else:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        lctx = {**found, "peaks": peaks, "chips": found["cell"]["chips"],
                "trace": trace, "counters": out["counters"],
                "measured": out["end_to_end"]}
        result["metrics"] = {}
        for m in found["per_layer"]:
            value = load_module("layer_metrics", m["name"]).read(lctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": xplane.top(trace["ops"]),
            "idle_gaps": [list(g) for g in
                          xplane.gaps_by_name(trace["gaps"])[:10]]}
        say("programs by device time: "
            + json.dumps(xplane.top(trace["programs"])))
        say("operations by device time: "
            + json.dumps(xplane.top(trace["ops"], 40)))
    result["device"] = device
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in check.items()}
    return result


def context(found, seed, seconds, devs, peaks, compiles, tracer=None,
            t_start=T_START):
    """What a driver's ``run`` is given."""
    return {**found, "seed": seed, "seconds": seconds, "t_start": t_start,
            "devices": devs, "peaks": peaks, "tracer": tracer,
            "compiles": compiles, "say": say,
            "device_info": lambda: device_info(devs)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    found = resolve(args.workload)
    chips = found["cell"]["chips"]

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"benchmark: {args.workload} needs {chips} TPU chip(s); jax "
            f"found {len(devs)} x {devs[0].platform}")
        return 2
    devs = devs[:chips]
    from benchmark import costs
    try:
        peaks = costs.peaks(devs[0].device_kind)
    except KeyError as e:
        say(f"benchmark: {e}")
        return 2
    from analytics_zoo_tpu.common.context import enable_compile_cache
    from analytics_zoo_tpu.observability import profile
    cache_dir = enable_compile_cache()
    compiles = profile.install()
    say(f"benchmark: {args.workload} seed {args.seed} on {len(devs)} x "
        f"{devs[0].device_kind}; compile cache {cache_dir}")

    tracer = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace")
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir,
                        max(args.seconds - TRACE_SECONDS, 0.0))
    driver = load_module("drivers", found["workload"]["driver"])
    out = driver.run(context(found, args.seed, args.seconds, devs, peaks,
                             compiles, tracer))
    # out: attempted, failed, end_to_end{}, counters{}, check{name:
    # [value, limit]}, device (read before the reference ran)
    if out["compiles_in_window"]:
        say(f"benchmark: {out['compiles_in_window']} compilation(s) inside "
            "the measured window: the warm-up missed a shape")
        return 3
    trace = None
    if args.trace:
        trace = tracer.reduce()
        if trace is None:
            say("benchmark: the trace holds no device operation")
            return 4
    result = result_line(found, out, peaks, trace)
    for k, v in result["check"].items():
        ok = v["value"] is not None and v["value"] <= v["limit"]
        say(f"check {k}: {v['value']} (limit {v['limit']}) "
            f"{'ok' if ok else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
