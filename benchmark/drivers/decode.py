"""Driver of the decode-serving cells: the model deployed as a user
deploys it (``InferenceModel(decode_capacity=...)`` +
``load_keras_net``), clients that call ``generate_stream`` and read
their ``TokenStream`` token by token on their own clock.

Closed loop (``kind: chat_closed_loop``): ``clients`` threads, each
sending its next request when the last token of the previous one
arrives.  Open loop (``kind: chat_open_loop``): Poisson arrivals at the
mix's fixed ``rate_hz``, a request's clock starting when it was due.
The load starts ``ramp_s`` before the window opens (set-up), so that the
window sees the steady state and not every slot admitted at once.

After the window, with the peak memory read and the engine closed, the
plain reference runs ONCE over a seeded sample of the finished requests
(the longest in it), each prompt with its served tokens.  The check
reads the gap by which each served token's logit lies below the
reference's best: the widest is printed, the mean of its square is the
number compared (``gap_numbers`` says why)."""

import gc
import importlib
import json
import threading
import time

import numpy as np


def deploy(ctx):
    """The served model with the seed's weights, warmed."""
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel
    cfg, spec = ctx["config"], ctx["workload"]
    adapter = importlib.import_module("benchmark.adapters." + spec["adapter"])
    ref = importlib.import_module("benchmark.reference." + spec["adapter"])
    net = adapter.build(cfg, {})
    # compile() is what gives a model a trainer to hold weights; "sgd"
    # keeps no optimizer state beside them
    net.compile("sgd", cfg["train"]["loss"])
    net.trainer.adopt_weights(ref.make_params(cfg, ctx["seed"]))
    eng = spec["engine"]
    im = InferenceModel(decode_capacity=eng["decode_capacity"],
                        decode_max_len=eng["decode_max_len"],
                        decode_prompt_buckets=tuple(
                            eng["decode_prompt_buckets"]))
    im.load_keras_net(net)      # builds the engine and warms every plan
    return im, ref


class Client:
    """A caller: its requests, and for each the submit time and the
    arrival time of every token on the client's clock."""

    def __init__(self, im, requests):
        self.im, self.requests = im, requests
        self.log = []       # (k, t_submit, [t_token...], [token...], error)

    def issue(self, k, t_sub):
        """Request ``k``, read token by token; ``t_sub`` is when it was
        submitted (closed loop) or DUE (open loop)."""
        import jax
        prompt, max_new = self.requests[k]
        times, toks, error = [], [], False
        try:
            with jax.profiler.TraceAnnotation("bench/submit"):
                stream = self.im.generate_stream(prompt, max_new)
            for tok in stream:
                times.append(time.perf_counter())
                toks.append(tok)
        except Exception:  # noqa: BLE001 — counted as failed
            error = True
        self.log.append((k, t_sub, times, toks, error))

    def run(self, stop_at):
        """Closed loop: the next request when the last token of the
        previous one has arrived."""
        k = 0
        while time.perf_counter() < stop_at and k < len(self.requests):
            self.issue(k, time.perf_counter())
            k += 1


def open_loop(client, traffic, duration_s, late):
    """Open loop: Poisson arrivals at the mix's fixed ``rate_hz``, drawn
    from its ``sizes_seed`` (every seed gets the same arrivals), issued
    whatever has completed; a request's clock starts when it was DUE.
    ``late`` receives how late the generator issued each request."""
    from benchmark import traffic as gen
    arrivals = gen.poisson_arrivals(gen.seeded(traffic["sizes_seed"], 4),
                                    traffic["rate_hz"], duration_s, 0.0, 0)
    arrivals = [(t, (k % len(client.requests), t))
                for k, (t, _) in enumerate(arrivals)]
    t_begin = time.perf_counter()
    records = gen.run_open_loop(
        lambda tag: client.issue(tag[0], t_begin + tag[1]), arrivals,
        traffic["workers"])
    late.extend(t_issue - t_due for t_due, t_issue, *_ in records)


def reduce_window(clients, t0, t1):
    """The window's end-to-end numbers from the clients' logs: requests
    SUBMITTED in [t0, t1) and tokens DELIVERED in [t0, t1).  A request
    that raised, or delivered another number of tokens than it asked
    for, failed."""
    ttft, gaps, tokens, attempted, failed = [], [], 0, 0, 0
    for c in clients:
        for k, t_sub, times, toks, error in c.log:
            max_new = c.requests[k][1]
            tokens += sum(t0 <= t < t1 for t in times)
            gaps += [b - a for a, b in zip(times, times[1:])
                     if t0 <= b < t1]
            if t0 <= t_sub < t1:
                attempted += 1
                if error or len(toks) != max_new:
                    failed += 1
                if times:
                    ttft.append(times[0] - t_sub)
    return {"ttft": ttft, "gaps": gaps, "tokens": tokens,
            "attempted": attempted, "failed": failed}


def sample_finished(clients, t0, seed, n):
    """``n`` requests that the window finished, drawn from the seed, the
    longest (prompt + served) among them."""
    done = [(c.requests[k][0], np.asarray(toks, np.int32))
            for c in clients for k, t_sub, times, toks, error in c.log
            if t_sub >= t0 and not error and len(toks) == c.requests[k][1]]
    if not done:
        return []
    done.sort(key=lambda r: (len(r[0]) + len(r[1])), reverse=True)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 77])
    rest = rng.permutation(len(done) - 1)[:n - 1] + 1
    return [done[0]] + [done[i] for i in rest]


def logit_gaps(ref, cfg, seed, sample, control=None, block=8):
    """The reference's full forward (float32, ``highest``) over each
    prompt with its served tokens, ``block`` rows at a time, rows padded
    to ``n_positions`` (causal, so the padding changes nothing before
    it).  Returns the gap, at every served position, between the
    reference's best logit and its logit of the served token; with
    ``control`` (a lower-precision mode) the token is the one THAT
    forward puts first at the same position."""
    import jax
    import jax.numpy as jnp
    width = cfg["n_positions"]
    params = ref.make_params(cfg, seed)

    @jax.jit
    def gap_of(p, x, picks):
        logits = ref.logits_fn(p, x, cfg, "f32")
        if control is not None:
            picks = jnp.argmax(ref.logits_fn(p, x, cfg, control), axis=-1)
        return jnp.max(logits, axis=-1) - jnp.take_along_axis(
            logits, picks[..., None].astype(jnp.int32), axis=-1)[..., 0]

    gaps = []
    for lo in range(0, len(sample), block):
        rows = np.zeros((block, width), np.int32)
        picks = np.zeros((block, width), np.int32)
        spans = []
        for i, (prompt, toks) in enumerate(sample[lo:lo + block]):
            n = min(len(toks), width - len(prompt))
            seq = np.concatenate([prompt, toks[:n]])
            rows[i, :len(seq)] = seq
            first = len(prompt) - 1     # the position that predicts toks[0]
            picks[i, first:first + n] = toks[:n]
            spans.append((i, first, n))
        g = np.asarray(gap_of(params, jnp.asarray(rows), jnp.asarray(picks)))
        gaps += [g[i, first:first + n] for i, first, n in spans]
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def gap_numbers(gaps):
    """The numbers of the check, from the gap at every served position.
    The arithmetic's error flips near-ties: with an error of size e,
    about e of the positions flip, each by about e.  So the WIDEST gap
    grows like e and is a maximum by nature; the mean gap like e^2; the
    mean SQUARED gap like e^3, which is the one that keeps the engine
    (one-pass bf16 products, float32 sums and activations) three times
    apart from the bfloat16 control.  That one is compared; the others
    are printed beside it."""
    if not len(gaps):
        return {"served_logit_gap_meansq": float("nan")}
    return {"served_logit_gap_meansq": float(np.mean(gaps ** 2)),
            "served_logit_gap_mean": float(np.mean(gaps)),
            "served_logit_gap_widest": float(np.max(gaps)),
            "off_best_share": float(np.mean(gaps > 0)),
            "positions": int(len(gaps))}


def run(ctx):
    import jax
    from benchmark import check, traffic as gen
    cfg, spec, say = ctx["config"], ctx["workload"], ctx["say"]
    traffic, tracer = spec["traffic"], ctx["tracer"]
    im, ref = deploy(ctx)
    engine = im.decode_engine
    t_deployed = time.perf_counter()
    pool = gen.chat_requests(traffic, cfg["vocab_size"], ctx["seed"])
    for b in spec["engine"]["decode_prompt_buckets"]:   # one warm request
        im.generate_stream(pool[0][0][:1].repeat(b), 8).result(timeout=120)

    ramp, late = traffic["ramp_s"], []
    t_begin = time.perf_counter()
    t0, t1 = t_begin + ramp, t_begin + ramp + ctx["seconds"]
    if traffic["kind"] == "chat_open_loop":
        clients = [Client(im, pool)]
        threads = [threading.Thread(target=open_loop, args=(
            clients[0], traffic, ramp + ctx["seconds"], late))]
    else:
        n = traffic["clients"]
        clients = [Client(im, pool[c::n]) for c in range(n)]
        threads = [threading.Thread(target=c.run, args=(t1,))
                   for c in clients]
    [t.start() for t in threads]
    time.sleep(max(t0 - time.perf_counter(), 0))
    c0 = ctx["compiles"].snapshot()["compiles"]
    s0 = engine.stats()
    setup_s = t0 - ctx["t_start"]
    s_traced = None
    while time.perf_counter() < t1:
        if tracer is not None:
            tracer.maybe_start(time.perf_counter() - t0)
            if s_traced is None and tracer.started is not None:
                s_traced = engine.stats()
        time.sleep(min(0.05, max(t1 - time.perf_counter(), 0)))
    s1 = engine.stats()
    if tracer is not None:
        tracer.stop()
    compiles = ctx["compiles"].snapshot()["compiles"] - c0
    [t.join(timeout=120) for t in threads]      # requests in flight finish
    hung = sum(t.is_alive() for t in threads)
    device = ctx["device_info"]()
    w = reduce_window(clients, t0, t1)
    w["failed"] += hung
    say(f"decode: deployed in {t_deployed - ctx['t_start']:.1f} s, set-up "
        f"{setup_s:.2f} s; window {t1 - t0:.1f} s: {w['attempted']} "
        f"requests, {w['tokens']} tokens; ttft ms at 50/75/90/95/99/100: "
        + "/".join(f"{1e3 * gen.percentile(w['ttft'], q):.1f}"
                   for q in (50, 75, 90, 95, 99, 100))
        + "; gap ms at 50/90/95/99/100: "
        + "/".join(f"{1e3 * gen.percentile(w['gaps'], q):.2f}"
                   for q in (50, 90, 95, 99, 100))
        + f" over {len(w['gaps'])} gaps")
    steps = s1["steps"] - s0["steps"]
    toks = s1["tokens"] - s0["tokens"]
    live = [len(c.requests[k][0]) + len(tk) / 2.0
            for c in clients for k, t_sub, tm, tk, _ in c.log if t_sub >= t0]
    counters = {"window_s": t1 - t0, "steps": steps, "engine_tokens": toks,
                "capacity": engine.capacity, "tokens": w["tokens"],
                "ttft_p95_ms": 1e3 * gen.percentile(w["ttft"], 95),
                "mean_live_positions": float(np.mean(live)) if live else 0.0,
                "admitted": s1["admitted"] - s0["admitted"],
                "fused_dispatches": (s1.get("fused_dispatches", 0)
                                     - s0.get("fused_dispatches", 0))}
    if late:
        counters["generator_late_p95_ms"] = 1e3 * gen.percentile(late, 95)
        say(f"decode: open loop at {traffic['rate_hz']} requests/s; the "
            f"generator ran {counters['generator_late_p95_ms']:.2f} ms late "
            "at its 95th percentile")
    if s_traced is not None:
        counters["traced_steps"] = s1["steps"] - s_traced["steps"]
        counters["traced_tokens"] = s1["tokens"] - s_traced["tokens"]

    # ------------------------------------------ free, then the reference
    sample = sample_finished(clients, t0, ctx["seed"],
                             spec["check"]["sample_requests"])
    im.close()
    del im, engine, clients, threads
    gc.collect()
    jax.clear_caches()
    gc.collect()
    live_b = sum(a.nbytes for a in jax.live_arrays())
    tr = time.perf_counter()
    gaps = logit_gaps(ref, cfg, ctx["seed"], sample)
    numbers = gap_numbers(gaps)
    control = None
    if ctx.get("control"):      # calibrate.py: the control, same sample
        control = gap_numbers(logit_gaps(ref, cfg, ctx["seed"], sample,
                                         control=ctx["control"]))
    say(f"decode: reference over {len(sample)} requests, {len(gaps)} served "
        f"tokens, took {time.perf_counter() - tr:.1f} s beside "
        f"{live_b / 1e9:.2f} GB still live; {json.dumps(numbers)}")
    return {"attempted": w["attempted"], "failed": w["failed"],
            "end_to_end": {
                "serve_tok_s": w["tokens"] / (t1 - t0),
                "serve_ttft_p99_ms": 1e3 * gen.percentile(w["ttft"], 99),
                "serve_gap_p95_ms": 1e3 * gen.percentile(w["gaps"], 95),
                "setup_s": setup_s},
            "counters": counters, "device": device, "control": control,
            "numbers": numbers,
            "compiles_in_window": compiles,
            "check": check.with_limits(numbers, spec["check"]["limits"])}


def calibrate(found, seeds, seconds=30.0):
    """The program's reading and the CONTROL's on the same sample, one
    short window a seed, all in one process.  The control of a served
    float32 model is the reference in bfloat16 put in the program's
    place; it need not decode: at each position of the same prompts and
    served tokens, the gap of the token that the lower precision puts
    first."""
    import jax
    from analytics_zoo_tpu.observability import profile
    from benchmark import costs, run as harness
    devs = jax.devices()[:found["cell"]["chips"]]
    for seed in seeds:
        ctx = harness.context(found, seed, seconds, devs,
                              costs.peaks(devs[0].device_kind),
                              profile.install(),
                              t_start=time.perf_counter())
        ctx["control"] = "bf16"
        out = run(ctx)
        print(json.dumps({
            "seed": seed, "program": out["numbers"],
            "control_bf16": out["control"], "failed": out["failed"],
            "tok_s": out["end_to_end"]["serve_tok_s"]}), flush=True)
