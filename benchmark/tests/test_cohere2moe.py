"""The ``cohere2_moe`` cell's files (PR 36): its configuration keeps the
catalog's widths, its cost functions' arithmetic, and each of its eight
readers on a fixture row built here: a traced run of 8 decode steps
(two fused windows of 4) over 48 slots, 4 layers, with the spans, the
kernel events and the scope paths the program writes.  A program without
them reads as nothing."""

import json

import pytest

from benchmark import costs_cohere2moe as costs
from benchmark import program_spans, routed_spans, run as harness

CELL = "cmdaplus-docqa-closed-5k"
MS = 1e6        # ns
HOST, DEV, DISPATCHER = "/host:CPU", "/device:TPU:0", 3
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STEP = "jit(stepk)/while/body/closed_call/"
ADMIT = "jit(admit)/zoo_prefill/"
KERNEL = ('%zoo_decode_attn_gqa.{} = (bf16[48,128,128]) custom-call(), '
          'custom_call_target="tpu_custom_call"')


def rows(with_names=True):
    """A traced window of 100 ms: two dispatches of 4 steps (their
    fanouts carry the routed counts), 32 calls of the decode kernel of
    1 ms (8 steps x 4 layers), expert work of 2 ms a call under the
    step programs' scopes and 6 ms under an admit plan's, the one
    admission of the window (a prompt of 4864 in the 5120 bucket)."""
    host = [(HOST, 0, "bench/traced", 0, 100 * MS, {})]
    ops, at = [], 0
    for w in range(2):
        lo = (5 + 45 * w) * MS
        if with_names:
            host += [
                (HOST, DISPATCHER, "zoo/decode/dispatch", lo, lo + MS,
                 {"k": 4, "live": 48, "kv_positions_live": 4 * 48 * 17000,
                  "kv_positions_read": 4 * 48 * 17920,
                  "kv_positions_window_skipped": 4 * 48 * 3312,
                  "pick_sorted": 0}),
                (HOST, DISPATCHER, "zoo/decode/fanout", lo + 40 * MS,
                 lo + 41 * MS,
                 {"tokens": 192, "evicted": 0, "steps": 4,
                  "moe_assignments": 4 * 48 * 32,
                  "moe_assignments_held": 4 * 48 * 2,
                  "moe_experts_hit": 4 * 4 * 7})]
    if with_names:
        host.append((HOST, DISPATCHER, "zoo/decode/admit", 59 * MS, 67 * MS,
                     {"bucket": 5120, "length": 4864, "slot": 7}))
    for call in range(32):
        name = KERNEL.format(call) if with_names else f"%fusion.{call}"
        ops.append((DEV, "XLA Ops", name, at, at + MS,
                    STEP + "zoo_decode_attention/jit(_decode_gqa_call)/"
                    "pallas_call:" if with_names else ""))
        at += MS
        scope = (STEP + "zoo_moe/zoo_moe_experts/etf,efd->etd/dot_general:"
                 if with_names else "")
        ops.append((DEV, "XLA Ops", f"%fusion.{100 + call} = f32[8,48,4096]",
                    at, at + 2 * MS // 4, scope))
        at += 2 * MS // 4
    ops.append((DEV, "XLA Ops", "%fusion.900 = f32[4608,4096]", 60 * MS,
                66 * MS, (ADMIT + "zoo_moe/zoo_moe_experts/ragged_dot:")
                if with_names else ""))
    ops.append((DEV, "XLA Ops", "%fusion.901 = f32[48,4096]", 70 * MS,
                74 * MS, (STEP + "zoo_moe/zoo_moe_shared/dot_general:")
                if with_names else ""))
    mods = [(DEV, "XLA Modules", "jit_stepk(1)", 0, 48 * MS, ""),
            (DEV, "XLA Modules", "jit_admit(2)", 60 * MS, 66 * MS, "")]
    return host + sorted(ops + mods, key=lambda r: (r[3], -r[4]))


def ctx_of(spans, **more):
    found = harness.resolve(CELL)
    return {**found, "peaks": PEAKS, "chips": 1, "program_spans": spans,
            "trace": {"busy_s": 58e-3, "devices": 1, "window_s": 0.1,
                      "programs": {"jit_stepk": 48e-3, "jit_admit": 6e-3}},
            "counters": {"traced_steps": 8, "traced_tokens": 8 * 48,
                         "mean_live_positions": 5200.0, "tokens": 30000,
                         "window_s": 30.0}, **more}


def read(metric, ctx):
    return harness.load_module("layer_metrics", metric).read(ctx)


NEW = ["moe_decode_step_mfu", "moe_decode_hbm_roofline",
       "decode_moe_device_share", "moe_experts_decode_roofline",
       "decode_attn_gqa_roofline", "decode_window_skipped_share",
       "moe_experts_hit_share", "moe_prefill_mxu_roofline"]
#: readers that were there and find this family's spans and scopes too
SHARED = ["decode_slot_occupancy", "serve_device_idle_share",
          "decode_attention_device_share", "decode_kv_read_efficiency",
          "decode_pick_argmax_share"]


def test_the_cell_resolves_and_lists_its_metrics():
    found = harness.resolve(CELL)
    assert found["workload"]["adapter"] == "cohere2moe"
    assert found["workload"]["driver"] == "decode"
    assert [m["name"] for m in found["end_to_end"]] == ["serve_tok_s",
                                                        "setup_s"]
    names = [m["name"] for m in found["per_layer"]]
    assert names[-8:] == NEW
    assert names[:-8] == SHARED
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            mod = harness.load_module("layer_metrics", m["name"])
            assert (m["layer"], m["unit"], m["source"], m["moves"]) == (
                mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES), m["name"]


def test_a_metric_of_the_cell_moves_what_the_cell_reports():
    """The cell reports ``serve_tok_s`` and ``setup_s``; a per-layer
    metric that lists it has to move one of them (the readers of first
    tokens move ``serve_ttft_p99_ms``, which it does not report)."""
    found = harness.resolve(CELL)
    reported = {m["name"] for m in found["end_to_end"]}
    assert reported == {"serve_tok_s", "setup_s"}
    for m in found["per_layer"]:
        assert m["moves"] in reported, m["name"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's config under the same key, but the
    three the file lists as reduced, and those state the published
    value."""
    cfg = harness.resolve(CELL)["config"]
    published = {
        "head_dim": 128, "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "num_attention_heads": 128,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 8,
        "num_shared_experts": 4, "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rope_theta": 50000,
        "rotary_pct": 1, "sliding_window": 4096, "vocab_size": 262144,
        "first_k_dense_replace": 0}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 32768)
    assert cfg["experts_held"] == [0, 8]
    assert cfg["num_experts_published"] == 128
    assert len(cfg["layer_types"]) == 32        # copied whole
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert set(cfg["assumed"]) >= {"shared_expert_combination_strategy",
                                   "router", "prefix_dense"}
    eng = harness.resolve(CELL)["workload"]["engine"]
    assert eng["decode_max_len"] == cfg["n_positions"] == 6144


def test_the_cost_functions_arithmetic():
    cfg = harness.resolve(CELL)["config"]
    assert costs.attention_params(cfg) == 142_606_336       # 142.6 M
    assert costs.expert_params(cfg) == 50_331_648           # 50.33 M
    assert costs.live_rows(cfg, 5200) == 3 * 4096 + 5200
    assert costs.live_rows(cfg, 100) == 4 * 100
    assert costs.held_pairs_per_token(cfg) == 0.5
    # 95.5 % of the held experts are hit at 48 slots
    assert costs.experts_hit_per_layer(cfg, 48) / 8 \
        == pytest.approx(0.955, abs=1e-3)
    layer = 142_606_336 + 4096 * 128 + 4.5 * 50_331_648
    assert costs.decode_flops_per_token(cfg, 5200) == pytest.approx(
        4 * 2 * layer + 4 * 128 * 128 * 17488 + 2 * 4096 * 32768)
    # a step at 48 slots, every held expert hit: 6.25 GB of weights and
    # 48 x 17,488 rows of 4 KiB
    every = costs.decode_bytes_per_step(cfg, 48, 5200, experts_hit=8)
    assert every == 2 * 3_122_679_808 - 2 * 5 * 4096 \
        + 4096 * 48 * 17488
    assert costs.decode_bytes_per_step(cfg, 48, 5200) < every
    # one layer's experts at 3 tokens an expert: the bytes bind
    assert costs.experts_step_floor_s(cfg, 48, 8, PEAKS) \
        == pytest.approx(8 * 50_331_648 * 2 / 819e9)
    assert costs.decode_attn_bytes_per_call(cfg, 1000) == 4096 * 1000
    # queries of a prompt of 5 see 1, 2, 3, 4, 5 keys; 1, 2, 2, 2, 2 in a
    # window of 2
    assert costs.visible_keys(5) == 15 and costs.visible_keys(5, 2) == 9
    assert costs.visible_keys(5, 8) == 15
    # an admission of 4864 tokens: 17.4 TFLOP, 88 ms at the peak
    pairs = 4864 * 4865 // 2 + 3 * (4096 * 4097 // 2 + 768 * 4096)
    assert costs.prefill_flops(cfg, 4864) == pytest.approx(
        4 * 2 * layer * 4864 + 4 * 128 * 128 * pairs + 2 * 4096 * 32768)
    assert costs.prefill_flops(cfg, 4864) == pytest.approx(17.4e12, rel=0.01)


def test_the_readers_on_the_fixture_rows():
    spans = program_spans.build(rows())
    ctx, cfg = ctx_of(spans), harness.resolve(CELL)["config"]
    assert read("moe_decode_step_mfu", ctx) == pytest.approx(
        100 * costs.decode_flops_per_token(cfg, 5200) * 1000 / 197e12)
    # 7 of 8 held experts hit a layer and a step
    assert routed_spans.experts_hit_per_layer_step(spans, cfg) == 7.0
    assert read("moe_experts_hit_share", ctx) == pytest.approx(87.5)
    need = costs.decode_bytes_per_step(cfg, 48, 5200, experts_hit=7.0)
    assert read("moe_decode_hbm_roofline", ctx) == pytest.approx(
        100 * (need / 819e9) / (48e-3 / 8))
    # zoo_moe: 32 x 0.5 ms in the steps, 6 ms in the admission, 4 ms shared
    assert read("decode_moe_device_share", ctx) == pytest.approx(
        100 * 26 / 58)
    # zoo_moe_experts in the step programs alone: 16 ms for 32 calls
    floor = costs.experts_step_floor_s(cfg, 48, 7.0, PEAKS)
    assert read("moe_experts_decode_roofline", ctx) == pytest.approx(
        100 * 32 * floor / 16e-3)
    # 32 kernel calls of 1 ms; a call reads 48 x 17,000 / 4 rows of 4 KiB
    per_call = 2 * 4 * 48 * 17000 / (8 * 4)
    assert read("decode_attn_gqa_roofline", ctx) == pytest.approx(
        100 * 32 * (4096 * per_call / 819e9) / 32e-3)
    assert read("decode_window_skipped_share", ctx) == pytest.approx(
        100 * 3312 / (3312 + 17920))
    # one admission of 4864 tokens whose program took 6 ms (a fixture's
    # time: the chip needs 88 ms)
    assert read("moe_prefill_mxu_roofline", ctx) == pytest.approx(
        100 * (costs.prefill_flops(cfg, 4864) / 197e12) / 6e-3)
    # the readers that were there: 8 steps by argmax alone, live / read
    # as the spans carry them, the attention scope's 32 ms of 58 busy
    assert read("decode_pick_argmax_share", ctx) == 100.0
    assert read("decode_kv_read_efficiency", ctx) == pytest.approx(
        100 * 17000 / 17920)
    assert read("decode_attention_device_share", ctx) == pytest.approx(
        100 * 32 / 58)


def test_an_admission_cut_by_the_traced_window_is_left_out():
    cut = [r if not r[2].startswith("jit_admit")
           else (*r[:3], 60 * MS, 101 * MS, r[5]) for r in rows()]
    ctx = ctx_of(program_spans.build(cut))
    assert read("moe_prefill_mxu_roofline", ctx) is None


def test_only_the_step_programs_operations_are_kept():
    spans = program_spans.build(rows())
    kept = routed_spans.step_programs_only(spans)
    assert len(kept.ops) == len(spans.ops) - 1      # the admission's is out
    assert spans.scope_seconds("zoo_moe_experts") == pytest.approx(22e-3)
    assert kept.scope_seconds("zoo_moe_experts") == pytest.approx(16e-3)
    assert routed_spans.stat_sum(spans, "decode/dispatch", "k") == (8, 2)
    assert routed_spans.stat_sum(spans, "decode/dispatch", "nope") == (0, 0)
    assert routed_spans.stat_sum(None, "decode/dispatch", "k") == (0, 0)


@pytest.mark.parametrize("metric", NEW[1:])
def test_a_trace_without_the_names_reads_as_nothing(metric):
    """A program that lacks the spans, the kernel and the scopes, and a
    run with no trace at all: every reader returns ``None`` and raises
    nothing (``moe_decode_step_mfu`` reads the clients' clock alone)."""
    bare = program_spans.build(rows(with_names=False))
    assert bare is not None and bare.ops
    empty = {"traced_steps": 0, "traced_tokens": 0}
    for spans in (bare, None):
        assert read(metric, ctx_of(spans, counters=empty, trace=None)) \
            is None
    if metric not in ("moe_decode_hbm_roofline",):
        assert read(metric, ctx_of(bare)) is None


def test_the_traffic_is_narrow_on_purpose():
    from benchmark import traffic
    t = json.load(open(harness.os.path.join(
        harness.HERE, "traffic", "docqa-closed-5k.json")))
    reqs = traffic.chat_requests(t, 32768, 3_000_000_019)
    p = [len(r[0]) for r in reqs]
    o = [r[1] for r in reqs]
    assert min(p) >= 4096 and max(p) <= 5632        # beyond the window
    # the issue's sizes: answers differ, so the slots stay out of lock step
    assert min(o) >= 256 and max(o) <= 512 and len(set(o)) > 100
    assert 370 <= sorted(o)[len(o) // 2] <= 398
    assert all(a + b <= 6144 for a, b in zip(p, o))
    assert max(max(r[0]) for r in reqs[:50]) < 32768
    # every seed draws the same multiset of sizes
    other = traffic.chat_requests(t, 32768, 5)
    assert sorted(p) == sorted(len(r[0]) for r in other)
