"""The ``ouro26-chat-closed-12`` cell's own pieces, on the CPU: the
configuration against the catalog row it comes from, the parameter
count, every cost function against hand numbers, the new readers on a
trace without their names and on a fixture, and a rehearsal of the cell
at a tiny size through the decode driver (``correct``, and with a fault
planted in the served program not)."""

import copy

import pytest

from benchmark import costs_ouro as costs
from benchmark import program_spans, run as harness
from benchmark.tests.test_benchmark import drive
from benchmark.tests.test_program_spans import (ctx_of, fixture_rows,
                                                without_names)

CELL = "ouro26-chat-closed-12"
#: the published ``config.json`` of ByteDance/Ouro-2.6B, as the catalog
#: of architectures holds it (its shape keys)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
NEW = ["looped_decode_hbm_roofline", "looped_prefill_mxu_roofline",
       "looped_decode_attn_roofline"]
#: the readers that were there before and read this cell unedited
SHARED = ["decode_slot_occupancy", "serve_device_idle_share",
          "decode_attention_device_share", "decode_kv_read_efficiency",
          "decode_pick_argmax_share", "admit_unnamed_share"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return harness.resolve(CELL)["config"]


def test_config_is_the_published_one_and_what_it_adds():
    cfg = config()
    assert cfg["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                             "blob/main/config.json")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    added = set(cfg) - set(PUBLISHED)
    assert added == {"name", "source", "described_as", "reference",
                     "reduced", "n_positions", "n_positions_why",
                     "deployment", "initializer_range", "assumed",
                     "departures", "train", "serve"}
    assert cfg["reduced"] == [] and cfg["deployment"][
        "chips_sharing_a_layer"] == 1
    for point in ("sandwich_norm", "per_pass_cache", "rope",
                  "no_biases_no_qk_norm"):
        assert point in cfg["assumed"], point
    assert any("exit gate" in d for d in cfg["departures"])


def test_the_cell_resolves_and_lists_its_metrics():
    found = harness.resolve(CELL)
    assert found["cell"]["chips"] == 1
    assert found["workload"]["adapter"] == "ouro"
    assert [m["name"] for m in found["end_to_end"]] == ["serve_tok_s",
                                                        "setup_s"]
    assert {m["name"] for m in found["per_layer"]} == set(NEW + SHARED)
    eng = found["workload"]["engine"]
    assert (eng["decode_capacity"], eng["decode_max_len"],
            eng["decode_prompt_buckets"]) == (12, 384, [128, 256])
    traffic = found["workload"]["traffic"]
    assert traffic["clients"] == 12 and traffic["max_total"] == 384
    assert traffic["prompt_len"]["max"] <= 256


def test_parameter_count():
    from benchmark.reference import ouro as ref
    cfg = config()
    # 48 x 51,388,416 + table and head 2 x 100,663,296 + the final norm
    # 2048 + the exit gate 2049
    assert costs.attention_params(cfg) == 4 * 2048 ** 2
    assert costs.mlp_params(cfg) == 3 * 2048 * 5632
    assert costs.layer_params(cfg) == 51_388_416
    assert costs.n_params(cfg) == ref.n_params(cfg) == 2_667_974_657


def test_costs_match_hand_counts():
    cfg = config()
    per_pass = 48 * (16_777_216 + 34_603_008)
    assert costs.matmul_params_per_pass(cfg) == per_pass
    row = 2 * 2 * 16 * 128
    assert costs.kv_row_bytes(cfg) == row
    # 4 passes x (2 x weights + 48 x 4 x 16 x 128 x 167) + the head
    assert costs.decode_flops_per_token(cfg, 167) == (
        4 * (2 * per_pass + 48 * 4 * 2048 * 167) + 2 * 2048 * 49152)
    # 12 slots at 167 live positions: 4 x 4.93 GB of layers, the head,
    # 192 slabs of keys and values
    need = costs.decode_bytes_per_step(cfg, 12, 167)
    assert need == (2 * 4 * 48 * 51_388_416 + 2 * 2048 * 49152
                    + row * 12 * 4 * 48 * 168)
    assert 22.9e9 < need < 23.3e9           # 28 ms at the HBM peak
    assert costs.decode_attn_bytes_per_call(cfg, 2004) == row * 2004
    flops = costs.prefill_flops(cfg, 128)
    assert flops == (4 * (128 * 2 * per_pass + 48 * 4 * 2048 * 128 * 129 // 2)
                     + 2 * 2048 * 49152)
    assert 2.5e12 < flops < 2.6e12
    # the tiny rehearsal's sizes: every count scales by hand
    tiny_cfg = tiny()["config"]
    assert costs.layer_params(tiny_cfg) == 4 * 128 * 128 + 3 * 128 * 96 \
        + 4 * 128
    assert costs.decode_bytes_per_step(tiny_cfg, 2, 10) == (
        2 * 3 * 2 * costs.layer_params(tiny_cfg) + 2 * 128 * 256
        + 2 * 2 * 2 * 64 * 2 * 3 * 2 * 11)
    # a prompt of 5: 3 passes x (5 x 2 x 2 layers x the products, 2
    # layers x 4 x 2 heads x 64 x 15 pairs) + the head once
    per_layer = 4 * 128 * 128 + 3 * 128 * 96
    assert costs.prefill_flops(tiny_cfg, 5) == (
        3 * (5 * 2 * 2 * per_layer + 2 * 4 * 2 * 64 * 15) + 2 * 128 * 256)


@pytest.mark.parametrize("metric", NEW)
def test_a_trace_without_the_names_reads_as_nothing(metric):
    """The parent of this cell's change, and a run with no trace at all:
    every new reader returns ``None`` and raises nothing."""
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    [entry] = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL]
    reader = harness.load_module("layer_metrics", metric)
    bare = program_spans.build(without_names(fixture_rows()))
    trace = {"busy_s": 36e-3, "devices": 1, "programs": {}}
    for spans in (bare, None):
        ctx = ctx_of(spans, CELL, trace=trace)
        ctx["counters"] = {}
        assert reader.read(ctx) is None


# ---------------------------------------- the readers on a fixture trace
MS = 1e6        # ns
HOST, DEV, DISPATCHER = "/host:CPU", "/device:TPU:0", 3
STEP = "jit(stepk)/while/body/closed_call/while/body/"
KERNEL = ('%zoo_decode_attn_gqa.{} = (bf16[12,16,128]) custom-call(), '
          'custom_call_target="tpu_custom_call"')


def fixture():
    """A traced window of 100 ms: a dispatch of 1 step over 12 slots of 4
    passes, 4 x 48 calls of the decode kernel of 0.2 ms, 0.1 ms of other
    work after each, and one admission (a prompt of 200 in the 256
    bucket) whose program ran wholly inside the window for 40 ms."""
    live = 12 * 4 * 48 * 150
    host = [(HOST, 0, "bench/traced", 0, 100 * MS, {}),
            (HOST, DISPATCHER, "zoo/decode/dispatch", 1 * MS, 2 * MS,
             {"k": 1, "live": 12, "kv_positions_live": live,
              "kv_positions_read": 12 * 4 * 48 * 256, "pick_sorted": 0,
              "passes": 4}),
            (HOST, DISPATCHER, "zoo/decode/admit", 58 * MS, 59 * MS,
             {"bucket": 256, "length": 200, "slot": 3, "passes": 4})]
    ops, at = [], 0
    for call in range(4 * 48):
        ops.append((DEV, "XLA Ops", KERNEL.format(call), at,
                    at + 2 * MS // 10, STEP + "zoo_decode_attention/"
                    "jit(_decode_gqa_call)/pallas_call:"))
        at += 2 * MS // 10
        ops.append((DEV, "XLA Ops", f"%fusion.{call} = f32[12,2048]", at,
                    at + MS // 10, STEP + "zoo_mlp/dot_general:"))
        at += MS // 10
    assert at == 57.6 * MS
    ops.append((DEV, "XLA Ops", "%fusion.900 = f32[256,2048]", 59 * MS,
                99 * MS, "jit(admit)/while/body/zoo_mlp/dot_general:"))
    mods = [(DEV, "XLA Modules", "jit_step(1)", 0, at, ""),
            (DEV, "XLA Modules", "jit_admit(2)", 59 * MS, 99 * MS, "")]
    return host + sorted(ops + mods, key=lambda r: (r[3], -r[4]))


def fixture_ctx():
    found = harness.resolve(CELL)
    spans = program_spans.build(fixture())
    return {**found, "peaks": PEAKS, "chips": 1, "program_spans": spans,
            "trace": {"busy_s": 97.6e-3, "devices": 1, "window_s": 0.1,
                      "programs": {"jit_step": 57.6e-3,
                                   "jit_admit": 40e-3}},
            "counters": {"traced_steps": 1, "traced_tokens": 12,
                         "mean_live_positions": 150.0, "tokens": 9000,
                         "window_s": 30.0}}


def test_the_readers_on_a_fixture_trace():
    ctx, cfg = fixture_ctx(), config()

    def read(metric):
        return harness.load_module("layer_metrics", metric).read(ctx)

    # one step of 57.6 ms that needs 12 slots' rows at 150 positions
    need = costs.decode_bytes_per_step(cfg, 12, 150.0)
    assert read("looped_decode_hbm_roofline") == pytest.approx(
        100 * need / 819e9 / 57.6e-3)
    # the admission of 200 positions, every pass, in 40 ms
    assert read("looped_prefill_mxu_roofline") == pytest.approx(
        100 * costs.prefill_flops(cfg, 200) / 197e12 / 40e-3)
    # 192 calls of 0.2 ms, each reading 12 x 150 rows of that pass
    floor = costs.decode_attn_bytes_per_call(cfg, 12 * 150) / 819e9
    assert read("looped_decode_attn_roofline") == pytest.approx(
        100 * floor / 0.2e-3)
    for metric in NEW:
        assert 0 < read(metric) < 100, metric
    # a program that ran its layers twice a token, said by its spans: the
    # readers take the passes from there, not from the configuration
    twice = program_spans.build([
        (p, t, n, lo, hi, {**x, "passes": 2} if "passes" in x else x)
        if isinstance(x, dict) else (p, t, n, lo, hi, x)
        for p, t, n, lo, hi, x in fixture()])
    ctx["program_spans"] = twice
    half = costs.decode_bytes_per_step({**cfg, "total_ut_steps": 2}, 12,
                                       150.0)
    assert read("looped_decode_hbm_roofline") == pytest.approx(
        100 * half / 819e9 / 57.6e-3)


# --------------------------------------------------- the cell, rehearsed
def tiny():
    """The cell's own files with the widths and the traffic shrunk to
    what a test can hold (2 layers run 3 times); driver, adapter and
    reference stay the cell's.  The limit does not: at these widths a
    random model's near-ties are fewer and coarser than at the published
    ones, so the rehearsal holds the program to 1e-4 (it reads about
    2e-8, the faults 4e-2 and more)."""
    found = copy.deepcopy(harness.resolve(CELL))
    found["config"].update(
        vocab_size=256, hidden_size=128, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        head_dim=64, total_ut_steps=3, rope_theta=1e4, n_positions=64)
    found["workload"]["check"]["limits"] = {"served_logit_gap_meansq": 1e-4}
    found["workload"]["engine"].update(
        decode_capacity=4, decode_max_len=64,
        decode_prompt_buckets=[8, 16, 32])
    found["workload"]["check"]["sample_requests"] = 12
    found["workload"]["traffic"].update(
        clients=4, ramp_s=0.3, pool=256, max_total=64,
        prompt_len=dict(median=10, sigma=0.7, min=2, max=32),
        output_len=dict(median=8, sigma=0.5, min=2, max=24))
    return found


@pytest.mark.parametrize("fault", [None, "fault_shared_cache",
                                   "fault_norm_once"])
def test_rehearsal_is_correct_and_a_planted_fault_is_not(fault):
    """Through the decode driver as a run makes it; with a fault of
    ``calibrate_ouro.py`` planted in the served program, the same check
    says NOT correct."""
    from benchmark import calibrate_ouro as cal
    undo = cal.plant(fault) if fault else (lambda: None)
    try:
        out, line = drive(tiny(), seconds=1.5)
    finally:
        undo()
    assert out["failed"] == 0 and out["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["correct"] is (fault is None), line["check"]
