"""The benchmark's own tests: CPU only, tiny sizes, quick.  They hold
the harness's contract (files resolve by name, the result line's keys,
no result without a chip), the yardstick's arithmetic (flops, bytes,
trace reduction), and the output check: a rehearsal of each driver is
``correct``; the lower-precision control and each planted fault is
not."""

import copy
import importlib
import json
import os
import re
import time

import pytest

from benchmark import check, costs, run as harness, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = harness.load_json(harness.ROOT, "BENCHMARK.json")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "check"]

TINY_LM = dict(vocab_size=211, n_positions=64, n_ctx=64, n_embd=32,
               n_layer=2, n_head=2)


def tiny(cell):
    """The cell's own files with the widths and the traffic shrunk to
    what a test can hold; driver and metrics stay the cell's.  A tiny
    leaf's norm is noisier than a real one's, so the tests hold the
    numbers to three times the cell's limits."""
    found = copy.deepcopy(harness.resolve(cell))
    found["config"].update(TINY_LM)
    limits = found["workload"]["check"]["limits"]
    limits.update({k: 3 * v for k, v in limits.items()})
    t = found["workload"]["traffic"]
    if found["workload"]["driver"] == "train":
        t.update(seq_len=32, batch=8, accum_steps=2, rows=512)
    else:
        found["workload"]["engine"].update(
            decode_capacity=4, decode_max_len=64,
            decode_prompt_buckets=[8, 16, 32])
        t.update(clients=4, ramp_s=0.3, pool=256, max_total=64,
                 prompt_len=dict(median=10, sigma=0.7, min=2, max=32),
                 output_len=dict(median=8, sigma=0.5, min=2, max=24))
    return found


def drive(found, seed=7, seconds=1.0, **extra):
    """The rest of a run, without the harness's look for a chip."""
    import jax
    from analytics_zoo_tpu.observability import profile
    driver = harness.load_module("drivers", found["workload"]["driver"])
    ctx = harness.context(found, seed, seconds, jax.devices()[:1], {},
                          profile.install(), t_start=time.perf_counter())
    out = driver.run({**ctx, **extra})
    return out, harness.result_line(found, out, {})


# ------------------------------------------------------------- contract
def test_manifest_resolves_every_file_by_name():
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert list(MANIFEST) == ["command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"]
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in MANIFEST[group]]
        assert len(set(names)) == len(names)
        for e in MANIFEST[group]:
            assert set(e) - {"workloads"} == want, e
            assert name.match(e["name"]), e["name"]
            assert len(e.get("why", "x")) <= 200
            if "unit" in e:
                assert unit.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            if "bound" in e:
                assert 0.01 <= e["bound"] <= 0.1
    assert all(name.match(w["traffic"]) for w in MANIFEST["workloads"])
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for cell in MANIFEST["workloads"]:
        found = harness.resolve(cell["name"])
        spec = found["workload"]
        assert spec["config"] == cell["config"]
        assert spec["traffic"]["name"] == cell["traffic"]
        assert spec["chips"] == cell["chips"] == 1
        assert spec["why"] == cell["why"] and len(cell["why"]) <= 200
        assert os.path.exists(os.path.join(
            harness.HERE, "drivers", spec["driver"] + ".py"))
        assert len(found["end_to_end"]) >= 2 and found["per_layer"]
        assert set(spec["check"]["limits"])
    for cfg in MANIFEST["configs"]:
        body = harness.load_json(harness.ROOT, cfg["file"])
        assert body["source"] == cfg["source"]
        assert body["reduced"] == cfg["reduced"]
        assert "assumed" in body and "departures" in body
    for m in MANIFEST["per_layer"]:
        reader = harness.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert m["moves"] in e2e and callable(reader.read)


def test_result_line_has_the_contracts_keys():
    found = harness.resolve("gpt2m-pretrain-1k")
    out = {"attempted": 3, "failed": 0, "compiles_in_window": 0,
           "end_to_end": {"train_samples_s": 1.5, "setup_s": 2.5},
           "counters": {"steps": 3, "batch": 16, "window_s": 1.0,
                        "flops_per_sample": 1e12},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 5},
           "check": {"loss2_rel": [1e-4, 1e-3]}}
    line = harness.result_line(found, out, {})
    assert list(line) == RESULT_KEYS and line["correct"] is True
    assert set(line["metrics"]) == {"train_samples_s", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 2.5, "unit": "s"}
    rows = json.load(open(os.path.join(HERE, "trace_fixture.json")))
    trace = xplane.reduce([tuple(r) for r in rows], 0, 20000)
    line = harness.result_line(found, out, costs.peaks("TPU v5 lite"), trace)
    assert list(line) == RESULT_KEYS[:4] + ["breakdown", "device", "check"]
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    # a reader with nothing to read (no stepprof counters) is left out
    assert "input_wait_share" not in line["metrics"]
    assert 0 < line["metrics"]["train_step_mfu"]["value"] < 100
    out["check"]["loss2_rel"][0] = float("nan")
    assert harness.result_line(found, out, {})["correct"] is False


def test_no_chip_no_result(capsys):
    rc = harness.main(["--workload", "gpt2m-pretrain-1k", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out == ""
    with pytest.raises(KeyError):
        costs.peaks("cpu")


# ------------------------------------------------------------ yardstick
def test_costs_match_hand_counts():
    cfg = harness.load_json(harness.HERE, "configs", "gpt2-medium.json")
    # 24 * (4 * 1024^2 + 2 * 1024 * 4096) + 1024 * 50257
    assert costs.lm_matmul_params(cfg) == 353_453_056
    # 3 * (2 * 353453056 * 1024 + 24 * 2 * 1024 * 1024 * 1025)
    assert costs.lm_train_flops_per_sample(cfg, 1024) == 2_326_385_393_664
    assert costs.causal_attention_flops(1024, 1024, queries=1) \
        == 4 * 1024 * 1024
    assert costs.lm_decode_flops_per_token(cfg, 100) \
        == 2 * 353_453_056 + 24 * 4 * 1024 * 100
    assert costs.lm_decode_bytes_per_step(cfg, 1000) \
        == 353_453_056 * 4 + 24 * 2 * 1024 * 1000 * 4
    assert costs.flash_flops(4, 1024, 1024) == 3 * 4 * 2 * 1024 * 1024 * 1025
    assert costs.flash_bytes(4, 1024, 1024) == 12 * 4 * 1024 * 1024 * 2
    from benchmark.reference import gpt2
    assert gpt2.n_params(cfg) == 406_238_289
    # stem 118,013,952; stage 0: 231,211,008 + 2 * 218,365,952; ...; the
    # classifier 2,048,000: the paper's "3.8 x 10^9" multiply-adds
    assert costs.resnet50_forward_macs() == 3_857_973_248


def test_trace_reduction_on_its_fixture():
    rows = [tuple(r) for r in json.load(open(os.path.join(
        HERE, "trace_fixture.json")))]
    t = xplane.reduce(rows, 0, 20000)
    assert t["devices"] == 1 and t["window_s"] == pytest.approx(20e-6)
    # busy: [1000, 7000] + [8000, 10000] + [12000, 15000]
    assert t["busy_s"] == pytest.approx(11e-6)
    # the while's own time is its 6000 less the 3000 nested in it
    assert t["ops"]["%while.1"] == pytest.approx(3e-6)
    assert t["ops"]["%fusion.1 kLoop"] == pytest.approx(2e-6)
    assert t["programs"] == {"jit_train_step": pytest.approx(9e-6),
                             "jit_admit": pytest.approx(3e-6)}
    assert t["pallas_calls"] == {"%custom-call.7": [pytest.approx(1e-6), 1]}
    gaps = dict(xplane.gaps_by_name(t["gaps"]))
    # [10000, 12000] lies under bench/submit for 1900 of its 2000 ns
    assert gaps["bench/submit"] == pytest.approx(2e-6)
    assert gaps["unattributed"] == pytest.approx(7e-6)
    assert xplane.reduce([r for r in rows if r[0] == "/host:CPU"]) is None


def test_trace_is_read_from_a_recorded_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    tracer = harness.Tracer(str(tmp_path), after_s=0.0)
    tracer.maybe_start(0.0)
    with jax.profiler.TraceAnnotation("bench/fit"):
        jnp.ones((64, 64)).sum().block_until_ready()
    tracer.stop()
    rows = xplane.load(str(tmp_path))
    assert {r[2] for r in rows} >= {"bench/traced", "bench/fit"}
    assert tracer.reduce() is None      # no device operation on the CPU


def test_every_seed_does_the_same_work():
    spec = harness.resolve("gpt2m-pretrain-1k")["workload"]["traffic"]
    small = dict(spec, rows=8, seq_len=16)
    a, _ = traffic.packed_tokens(small, 50257, 1)
    b, _ = traffic.packed_tokens(small, 50257, 2**31 + 5)
    assert a.shape == b.shape == (8, 16) and (a != b).any()
    mix = harness.load_json(harness.HERE, "traffic", "chat-closed.json")
    one = traffic.chat_requests(mix, 50257, 3)
    two = traffic.chat_requests(mix, 50257, 3_000_000_000)
    sizes = lambda reqs: sorted((len(p), n) for p, n in reqs)
    assert sizes(one) == sizes(two) and len(one) == mix["pool"]
    assert all(len(p) + n <= mix["max_total"] for p, n in one)
    assert any((p != q).any() if len(p) == len(q) else True
               for (p, _), (q, _) in zip(one, two))


# ---------------------------------------------------- rehearsals, check
def test_train_rehearsal_is_correct_and_names_its_device():
    out, line = drive(tiny("gpt2m-pretrain-1k"))
    assert line["correct"] is True, line["check"]
    assert line["device"]["platform"] == "cpu"      # never a chip's number
    assert out["attempted"] > 0 and out["compiles_in_window"] == 0
    assert set(line["check"]) == {
        "loss2_rel", "grad1_norm_gap", "grad1_norm_gap_median",
        "dparam_norm_gap", "dparam_norm_gap_median"}


def test_lower_precision_control_is_not_correct():
    """The reference in fp8 put in the program's place (the step below
    the configuration's bfloat16) fails one of the cell's limits."""
    from benchmark import calibrate
    found = tiny("gpt2m-pretrain-1k")
    cfg, spec = found["config"], found["workload"]
    ref = importlib.import_module("benchmark.reference.gpt2")
    x, y = traffic.packed_tokens(spec["traffic"], cfg["vocab_size"], 3)
    xs, ys = x[:24].reshape(3, 8, -1), y[:24].reshape(3, 8, -1)
    want = ref.train_steps(cfg, 3, xs, ys, 3, 4)
    got = ref.train_steps(
        cfg, 3, xs, ys, 3, 4,
        mode=calibrate.CONTROL_MODE[cfg["train"]["compute_dtype"]])
    numbers, _ = check.train_numbers(got, want)
    limits = spec["check"]["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers


def _plant(monkeypatch, fault):
    """Break the timed path underneath: the trainer's step builder."""
    import jax
    from analytics_zoo_tpu.train import trainer as tr
    real = tr.build_train_step

    def build(model, loss_fn, optimizer, compute_dtype=None, **kw):
        plain = real(model, loss_fn, optimizer, compute_dtype=compute_dtype,
                     jit=False, accum_steps=1)

        def step(params, mstate, opt, rng, x, y):
            if fault == "state_unchanged":
                loss = plain(params, mstate, opt, rng, x[0], y[0])[3]
                return params, mstate, opt, loss
            # half of the batch left out, the mean taken over the rest:
            # one microbatch of the two
            return plain(params, mstate, opt, rng, x[0], y[0])
        return jax.jit(step)

    monkeypatch.setattr(tr, "build_train_step", build)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    _, line = drive(tiny("gpt2m-pretrain-1k"))
    assert line["correct"] is False, line["check"]


@pytest.mark.parametrize("fault", [None, "token_altered", "open_loop"])
def test_decode_rehearsal_and_an_altered_token(monkeypatch, fault):
    found = tiny("gpt2m-chat-closed")
    if fault == "token_altered":
        from analytics_zoo_tpu.pipeline.inference import decode
        real = decode._sample
        monkeypatch.setattr(
            decode, "_sample",
            lambda logits, *a, **k: (real(logits, *a, **k) + 1)
            % logits.shape[-1])
    if fault == "open_loop":    # the same mix as arrivals: data alone
        found["workload"]["traffic"].update(
            kind="chat_open_loop", rate_hz=20.0, workers=8)
    # the control beside it: the bfloat16 reference in the program's
    # place, read on the same prompts and served tokens
    out, line = drive(found, seconds=1.5,
                      control="bf16" if fault is None else None)
    assert out["failed"] == 0 and out["attempted"] > 0
    if fault is None:
        key = "served_logit_gap_meansq"
        assert out["control"][key] > 3 * out["numbers"][key], out["control"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tok_s", "serve_ttft_p99_ms",
                                    "serve_gap_p95_ms", "setup_s"}
    assert line["correct"] is (fault != "token_altered"), line["check"]
    assert ("generator_late_p95_ms" in out["counters"]) \
        is (fault == "open_loop")
