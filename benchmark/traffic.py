"""The one general traffic generator.  A cell's traffic is DATA: the
``traffic`` object of ``workloads/<cell>.json``.  Everything is drawn
from ``--seed``; the SIZES (lengths, counts, arrivals) come from the
mix's own fixed ``sizes_seed`` and are only re-ordered by ``--seed``, so
every seed does the same amount of work.

``poisson_arrivals``, ``ramp_arrivals``, ``run_open_loop`` and
``run_closed_loop`` are copies of ``bench.py``'s ``_poisson_arrivals``,
``_ramp_arrivals``, ``_run_open_loop`` and ``_run_closed_loop`` at
commit 258f0d6 (lines 2117-2232), with the program's exception classes
taken out (any exception is an "error" outcome, named by its class).
These copies are the yardstick from now on."""

import threading
import time

import numpy as np


def seeded(seed, salt=0):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  salt])


# ------------------------------------------------------------- training
def packed_tokens(traffic, vocab, seed):
    """``rows`` packed sequences of ``seq_len``+1 tokens with log-uniform
    (Zipf-like) ranks, as ``chip_smoke.lm_tokens``: inputs and next-token
    targets, int32.  Every row differs."""
    rng = seeded(seed, 1)
    shape = (traffic["rows"], traffic["seq_len"] + 1)
    seq = np.floor(np.exp(rng.uniform(0.0, np.log(vocab), shape))) - 1
    seq = np.clip(seq, 0, vocab - 1).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


# -------------------------------------------------------------- serving
def _lognormal_lengths(rng, n, spec):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def chat_requests(traffic, vocab, seed):
    """``pool`` requests as (prompt ids, max_new_tokens): log-normal
    prompt and output lengths from the mix's ``sizes_seed`` (the same
    multiset for every ``--seed``), order and token ids from ``--seed``;
    prompt + output is cut to ``max_total``.  No shared prefixes: ids are
    uniform over the vocabulary."""
    sizes = seeded(traffic["sizes_seed"], 2)
    n = traffic["pool"]
    p_len = _lognormal_lengths(sizes, n, traffic["prompt_len"])
    o_len = _lognormal_lengths(sizes, n, traffic["output_len"])
    o_len = np.minimum(o_len, traffic["max_total"] - p_len)
    rng = seeded(seed, 3)
    order = rng.permutation(n)
    return [(rng.integers(0, vocab, int(p_len[i])).astype(np.int32),
             int(o_len[i])) for i in order]


def poisson_arrivals(rng, rate_hz, duration_s, t0, tag):
    """Open-loop Poisson arrival offsets: exponential gaps at
    ``rate_hz``, offset by ``t0``, tagged for per-phase accounting."""
    out = []
    t = t0
    while True:
        t += rng.exponential(1.0 / rate_hz)
        if t >= t0 + duration_s:
            return out
        out.append((t, tag))


def ramp_arrivals(rng, rate0, rate1, duration_s, t0, tag):
    """Linearly increasing arrival rate (thinning a Poisson stream at
    the peak rate)."""
    out = []
    t = t0
    while True:
        t += rng.exponential(1.0 / rate1)
        if t >= t0 + duration_s:
            return out
        frac = (t - t0) / duration_s
        if rng.random() < (rate0 + (rate1 - rate0) * frac) / rate1:
            out.append((t, tag))


def run_open_loop(issue_one, arrivals, n_workers=24):
    """Drive a sorted ``[(t_offset, tag), ...]`` schedule open-loop:
    workers issue each request at its scheduled time REGARDLESS of
    completions.  Returns ``(t_due, t_issue, tag, outcome, latency_s)``
    records; latency counts from when the request was DUE."""
    idx = [0]
    lock = threading.Lock()
    records = []
    t0 = time.perf_counter()

    def worker():
        while True:
            with lock:
                i = idx[0]
                if i >= len(arrivals):
                    return
                idx[0] += 1
            t_sched, tag = arrivals[i]
            delay = t0 + t_sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t_issue = time.perf_counter()
            outcome = "ok"
            try:
                issue_one(tag)
            except Exception as e:  # noqa: BLE001 — counted by the caller
                outcome = "error:" + type(e).__name__
            lat = time.perf_counter() - (t0 + t_sched)
            with lock:
                records.append((t_sched, t_issue - t0, tag, outcome, lat))

    threads = [threading.Thread(target=worker) for _ in range(n_workers)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    return records


def run_closed_loop(issue_one, n_clients, duration_s):
    """Closed loop: ``n_clients`` workers each issue back-to-back until
    ``duration_s`` has passed; a request begun inside the window is
    finished.  ``issue_one(client, k)`` is the client's k-th request.
    Returns ``(t0, records)``, records ``(client, k, outcome,
    t_submit - t0, latency_s)``."""
    records = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    stop = t0 + duration_s

    def worker(client):
        mine, k = [], 0
        while time.perf_counter() < stop:
            t_sub = time.perf_counter()
            outcome = "ok"
            try:
                issue_one(client, k)
            except Exception as e:  # noqa: BLE001
                outcome = "error:" + type(e).__name__
            mine.append((client, k, outcome, t_sub - t0,
                         time.perf_counter() - t_sub))
            k += 1
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(n_clients)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    return t0, records


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))
