"""The ``granite4h-chat-closed-64`` cell's own pieces, on the CPU: the
configuration against the catalog row it comes from, the parameter
count, every cost function against hand numbers at the cell's sizes,
the new readers on a trace without their names, and a rehearsal of the
cell at a tiny size through the decode driver (``correct``, and with a
fault planted in the served program not)."""

import copy

import pytest

from benchmark import costs_granitehybrid as costs
from benchmark import program_spans, run as harness
from benchmark.tests.test_benchmark import drive
from benchmark.tests.test_program_spans import (ctx_of, fixture_rows,
                                                without_names)

CELL = "granite4h-chat-closed-64"
#: the published ``config.json`` of ibm-granite/granite-4.0-h-micro, as
#: the catalog of architectures holds it (its shape keys)
PUBLISHED = {
    "attention_bias": False,
    "attention_multiplier": 0.015625,
    "embedding_multiplier": 12,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "logits_scaling": 8,
    "mamba_chunk_size": 256,
    "mamba_conv_bias": True,
    "mamba_d_conv": 4,
    "mamba_d_head": 64,
    "mamba_d_state": 128,
    "mamba_expand": 2,
    "mamba_n_groups": 1,
    "mamba_n_heads": 64,
    "mamba_proj_bias": False,
    "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm",
    "num_attention_heads": 32,
    "num_experts_per_tok": 0,
    "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "num_local_experts": 0,
    "position_embedding_type": "nope",
    "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 10000,
    "shared_intermediate_size": 8192,
    "tie_word_embeddings": True,
    "vocab_size": 100352,
    "layer_types": ["attention" if i in (5, 15, 25, 35) else "mamba"
                    for i in range(40)],
}
NEW = ["ssm_decode_state_roofline", "ssm_prefill_scan_roofline",
       "decode_ssm_device_share", "hybrid_decode_step_mfu",
       "hybrid_decode_hbm_roofline", "hybrid_prefill_mxu_roofline"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return harness.resolve(CELL)["config"]


def test_config_is_the_published_one_and_what_it_adds():
    cfg = config()
    assert cfg["source"] == ("https://huggingface.co/ibm-granite/"
                             "granite-4.0-h-micro/blob/main/config.json")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    added = set(cfg) - set(PUBLISHED)
    assert added == {"name", "source", "described_as", "reference",
                     "reduced", "n_positions", "n_positions_why",
                     "deployment", "initializer_range", "assumed",
                     "departures", "train", "serve"}
    assert cfg["reduced"] == [] and cfg["deployment"][
        "chips_sharing_a_layer"] == 1
    assert cfg["assumed"]["ssm_state_dtype"].startswith("float32")


def test_parameter_count():
    from benchmark.reference import granitehybrid as ref
    cfg = config()
    # 36 x 76,182,976 + 4 x 60,821,504 + 100352 x 2048 + 2048
    assert costs.mixer_params(cfg) == 25_847_232
    assert costs.mixer_params(cfg) + costs.mlp_params(cfg) + 2 * 2048 \
        == 76_182_976
    assert costs.attention_params(cfg) + costs.mlp_params(cfg) + 2 * 2048 \
        == 60_821_504
    assert costs.n_params(cfg) == ref.n_params(cfg) == 3_191_396_096


def test_costs_match_hand_counts():
    cfg = config()
    per_token = 36 * (17_432_576 + 8_388_608) + 4 * 10_485_760 \
        + 40 * 50_331_648
    assert costs.matmul_params_per_token(cfg) == per_token
    state = 64 * 64 * 128
    assert costs.state_elems(cfg) == state
    # 2 x weights + 36 x (conv 2 x 4 x 4352 + state 5 x 524288) + 4 x 4 x
    # 32 x 64 x 300 live rows + the head 2 x 2048 x 100352
    assert costs.decode_flops_per_token(cfg, 300) == (
        2 * per_token + 36 * (8 * 4352 + 5 * state)
        + 4 * 4 * 32 * 64 * 300 + 2 * 2048 * 100352)
    # 64 slots, 384 positions: weights 6.38 GB, state 9.66 GB read and
    # written, windows, keys and values
    need = costs.decode_bytes_per_step(cfg, 64, 384)
    assert need == (2 * 3_191_396_096 + 8 * 64 * 36 * state
                    + 4 * 64 * 36 * 3 * 4352 + 2 * 64 * 4 * 384 * 2 * 512)
    assert 16.3e9 < need < 16.5e9
    assert costs.ssm_decode_bytes_per_call(cfg, 64) == 4 * 64 * (
        2 * state + 2 * 4096 + 64 + 2 * 128)
    assert costs.causal_pairs(1024, 256) == 4 * 256 * 257 // 2
    assert costs.causal_pairs(300, 256) == 256 * 257 // 2 + 44 * 45 // 2
    assert costs.scan_flops(cfg, 1024) == (
        2 * 4 * 256 * 257 // 2 * (128 + 4096) + 4 * 1024 * 4096 * 128)
    assert costs.scan_bytes(cfg, 1024) == (
        1024 * (2 * (4096 + 256) + 4 * (64 + 4096)) + 4 * state)
    # a 1024-token prompt: about 6.1 TFLOP, 31 ms at the MXU's peak
    flops = costs.prefill_flops(cfg, 1024)
    assert flops == (1024 * (2 * per_token + 36 * 8 * 4352)
                     + 36 * costs.scan_flops(cfg, 1024)
                     + 4 * 4 * 32 * 64 * 1024 * 1025 // 2
                     + 2 * 2048 * 100352)
    assert 6.0e12 < flops < 6.3e12
    # a layer's scan over 1024 positions: 3.3 GFLOP (16.5 us) against
    # 28.0 MB in and out (34.2 us): its bytes bind it
    assert costs.scan_floor_s(cfg, 1024, PEAKS) == pytest.approx(
        costs.scan_bytes(cfg, 1024) / 819e9)
    assert costs.scan_flops(cfg, 1024) / 197e12 < 17e-6


@pytest.mark.parametrize("metric", NEW)
def test_a_trace_without_the_names_reads_as_nothing(metric):
    """The parent of the PR that brought the names, and a run with no
    trace at all: every new reader returns ``None`` and raises
    nothing (the host-clock MFU reads the counters, none here)."""
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


def tiny():
    """The cell's own files with the widths and the traffic shrunk to
    what a test can hold (one period of ten layers); driver, adapter and
    reference stay the cell's.  The limit does not: at these widths a
    random model's near-ties are fewer and coarser than at the published
    ones, so the rehearsal holds the program to 1e-4 (the faults read
    1e-3 and more)."""
    found = copy.deepcopy(harness.resolve(CELL))
    found["config"].update(
        vocab_size=256, hidden_size=64, shared_intermediate_size=96,
        num_hidden_layers=10, num_attention_heads=4, num_key_value_heads=2,
        mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
        mamba_chunk_size=8, n_positions=64, initializer_range=0.2,
        embedding_multiplier=1.5,
        layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
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


@pytest.mark.parametrize("fault", [None, "fault_bucket_end",
                                   "fault_window_zero"])
def test_rehearsal_is_correct_and_a_planted_fault_is_not(fault):
    """Through the decode driver as a run makes it; with a fault of
    ``calibrate_granitehybrid.py`` planted in the served program, the
    same check says NOT correct."""
    from benchmark import calibrate_granitehybrid as cal
    undo = cal.plant(fault) if fault else (lambda: None)
    try:
        out, line = drive(tiny(), seconds=1.5)
    finally:
        undo()
    assert out["failed"] == 0 and out["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["correct"] is (fault is None), line["check"]


# ---------------------------------------- the readers on a fixture trace
MS = 1e6        # ns
HOST, DEV, DISPATCHER = "/host:CPU", "/device:TPU:0", 3
STEP = "jit(stepk)/while/body/closed_call/"
ADMIT = "jit(admit)/zoo_prefill/"
KERNEL = ('%zoo_ssm_decode.{} = (f32[64,64,64,128]) custom-call(), '
          'custom_call_target="tpu_custom_call"')


def fixture():
    """A traced window of 100 ms: a dispatch of 2 steps over 64 slots, 2
    x 36 calls of the state-update kernel of 0.6 ms under the step
    program's scope, 0.05 ms of in_proj a call under the mixer's scope,
    and one admission (a prompt of 900 in the 1024 bucket) whose program
    ran wholly inside the window: 4 ms under the scan's scope, 2 ms under
    the mixer's, 44 ms elsewhere."""
    host = [(HOST, 0, "bench/traced", 0, 100 * MS, {}),
            (HOST, DISPATCHER, "zoo/decode/dispatch", 5 * MS, 6 * MS,
             {"k": 2, "live": 64, "kv_positions_live": 100,
              "kv_positions_read": 200, "pick_sorted": 0})]
    host.append((HOST, DISPATCHER, "zoo/decode/admit", 47 * MS, 48 * MS,
                 {"bucket": 1024, "length": 900, "slot": 3}))
    ops, at = [], 0
    for call in range(2 * 36):
        ops.append((DEV, "XLA Ops", KERNEL.format(call), at,
                    at + 6 * MS // 10, STEP + "zoo_ssm/zoo_ssm_scan/"
                    "jit(_ssm_decode_call)/pallas_call:"))
        at += 6 * MS // 10
        ops.append((DEV, "XLA Ops", f"%fusion.{call} = f32[64,8512]", at,
                    at + MS // 20, STEP + "zoo_ssm/dot_general:"))
        at += MS // 20
    assert at == 46.8 * MS
    ops += [(DEV, "XLA Ops", "%fusion.900 = f32[1024,64,64]", 48 * MS,
             52 * MS, ADMIT + "zoo_ssm/zoo_ssm_scan/dot_general:"),
            (DEV, "XLA Ops", "%fusion.901 = f32[1024,8512]", 52 * MS,
             54 * MS, ADMIT + "zoo_ssm/dot_general:"),
            (DEV, "XLA Ops", "%fusion.902 = f32[1024,16384]", 54 * MS,
             98 * MS, ADMIT + "dot_general:")]
    mods = [(DEV, "XLA Modules", "jit_stepk(1)", 0, at, ""),
            (DEV, "XLA Modules", "jit_admit(2)", 48 * MS, 98 * MS, "")]
    return host + sorted(ops + mods, key=lambda r: (r[3], -r[4]))


def fixture_ctx():
    found = harness.resolve(CELL)
    spans = program_spans.build(fixture())
    return {**found, "peaks": PEAKS, "chips": 1, "program_spans": spans,
            "trace": {"busy_s": 96.8e-3, "devices": 1, "window_s": 0.1,
                      "programs": {"jit_stepk": 46.8e-3,
                                   "jit_admit": 50e-3}},
            "counters": {"traced_steps": 2, "traced_tokens": 2 * 60,
                         "mean_live_positions": 384.0, "tokens": 60000,
                         "window_s": 30.0}}


def test_the_readers_on_a_fixture_trace():
    ctx, cfg = fixture_ctx(), config()

    def read(metric):
        return harness.load_module("layer_metrics", metric).read(ctx)

    # 72 calls of 0.6 ms, each needing 64 slots' state and io (0.33 ms)
    floor = costs.ssm_decode_bytes_per_call(cfg, 64) / 819e9
    assert read("ssm_decode_state_roofline") == pytest.approx(
        100 * floor / 0.6e-3)
    # 36 scans at the prompt's own 900 positions, in the 4 ms under the
    # scan's scope of the admit program (not the step's kernel calls)
    assert read("ssm_prefill_scan_roofline") == pytest.approx(
        100 * 36 * costs.scan_floor_s(cfg, 900, PEAKS) / 4e-3)
    # the mixers' self time: 46.8 ms in the steps, 6 ms in the admission
    assert read("decode_ssm_device_share") == pytest.approx(
        100 * 52.8e-3 / 96.8e-3)
    assert read("hybrid_prefill_mxu_roofline") == pytest.approx(
        100 * costs.prefill_flops(cfg, 900) / 197e12 / 50e-3)
    need = costs.decode_bytes_per_step(cfg, 60, 384.0)
    assert read("hybrid_decode_hbm_roofline") == pytest.approx(
        100 * need / 819e9 / (46.8e-3 / 2))
    assert read("hybrid_decode_step_mfu") == pytest.approx(
        100 * costs.decode_flops_per_token(cfg, 384.0) * 2000 / 197e12)
    for metric in NEW:
        assert 0 < read(metric) < 100, metric
