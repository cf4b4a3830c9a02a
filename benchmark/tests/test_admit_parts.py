"""``benchmark/admit_parts.py`` and the four ``admit_*`` readers on a
synthetic trace: each operation of a whole admit program goes to the
innermost part name in its scope path, nested operations count once,
what lies under no part is ``"unnamed"``, and a trace or program
without the names reads as nothing."""

import pytest

from benchmark import admit_parts, program_spans, run as harness
from benchmark import costs_cohere2moe as c2

CELL = "cmdaplus-docqa-closed-5k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6        # ns
HOST, DEV = "/host:CPU", "/device:TPU:0"
ADMIT = "jit(admit)/"
NEW = ["admit_unnamed_share", "admit_attention_mxu_roofline",
       "admit_experts_mxu_roofline", "admit_dense_mxu_roofline"]


def rows(admit=ADMIT):
    """A traced window of 100 ms: a step program (0-20 ms) whose
    projections must not count, one admission (a prompt of 4864 in the
    5120 bucket) whose program ran wholly inside the window (20-80 ms),
    and one cut by the window's end (90-100 ms).  Inside the whole one:
    a ``while`` of 10 ms around the flash kernel (9 ms) under
    ``zoo_attn_core``; 10 ms of projections; 15 ms of held experts, 10
    of shared experts and 1 of router under ``zoo_moe``; 4 ms of a norm
    nested in the projections' path (innermost wins); 5 ms with no part
    (2 without any metadata, 3 under the program alone); 5 ms of
    head."""
    host = [(HOST, 0, "bench/traced", 0, 100 * MS, {}),
            (HOST, 3, "zoo/decode/admit", 19 * MS, 20 * MS,
             {"bucket": 5120, "length": 4864, "slot": 1})]
    ops = [
        (0, 20, "jit(stepk)/zoo_decode_attention/zoo_attn_proj/dot:"),
        (20, 30, admit + "zoo_attn_core/while"),
        (21, 30, admit + "zoo_attn_core/jit(_flash_fwd_call)/pallas_call:"),
        (30, 40, admit + "zoo_attn_proj/dot_general:"),
        (40, 55, admit + "zoo_moe/zoo_moe_experts/dot_general:"),
        (55, 65, admit + "zoo_moe/zoo_moe_shared/dot_general:"),
        (65, 66, admit + "zoo_moe/zoo_moe_router/dot_general:"),
        (66, 70, admit + "zoo_attn_proj/jit(_where)/zoo_norm/add:"),
        (70, 72, ""),
        (72, 75, admit + "copy:"),
        (75, 80, admit + "zoo_head/dot_general:"),
        (90, 110, admit + "zoo_attn_proj/dot_general:")]
    dev = [(DEV, "XLA Ops", f"%fusion.{k} = f32[8]", lo * MS, hi * MS, path)
           for k, (lo, hi, path) in enumerate(ops)]
    dev += [(DEV, "XLA Modules", "jit_stepk(1)", 0, 20 * MS, ""),
            (DEV, "XLA Modules", "jit_admit(2)", 20 * MS, 80 * MS, ""),
            (DEV, "XLA Modules", "jit_admit(3)", 90 * MS, 110 * MS, "")]
    return host + sorted(dev, key=lambda r: (r[3], -r[4]))


def ctx_of(spans, cell=CELL):
    return {**harness.resolve(cell), "peaks": PEAKS, "chips": 1,
            "program_spans": spans,
            "trace": {"busy_s": 0.08, "devices": 1, "window_s": 0.1}}


def read(metric, ctx):
    return harness.load_module("layer_metrics", metric).read(ctx)


def test_innermost_part_of_a_scope_path():
    names = ("zoo_norm", "zoo_attn_proj", "zoo_moe_experts", "zoo_sample")
    assert admit_parts.innermost(
        "jit(admit)/zoo_attn_proj/jit(_where)/zoo_norm/add:", names) \
        == "zoo_norm"
    assert admit_parts.innermost(
        "jit(admit)/zoo_sample/jit(zoo_sample)/cond", names) == "zoo_sample"
    # a name is whole between delimiters: ``zoo_moe`` is no part, and
    # ``zoo_moe_experts_x`` is not ``zoo_moe_experts``
    assert admit_parts.innermost("jit(admit)/zoo_moe/dot:", names) \
        == admit_parts.UNNAMED
    assert admit_parts.innermost("jit(admit)/zoo_moe_experts_x/dot:",
                                 names) == admit_parts.UNNAMED
    assert admit_parts.innermost("", names) == admit_parts.UNNAMED


def test_split_of_the_whole_admit_programs(capsys):
    s = admit_parts.split(ctx_of(program_spans.build(rows())))
    assert s.count == 1 and s.programs_s == pytest.approx(60e-3)
    assert s.lengths == [4864] and s.buckets == [5120]
    want = {"zoo_attn_core": 10e-3, "zoo_attn_proj": 10e-3,
            "zoo_moe_experts": 15e-3, "zoo_moe_shared": 10e-3,
            "zoo_moe_router": 1e-3, "zoo_norm": 4e-3, "zoo_head": 5e-3,
            admit_parts.UNNAMED: 5e-3}
    assert set(s.seconds) == set(want)
    for part, sec in want.items():
        assert s.seconds[part] == pytest.approx(sec), part
    # the parts partition the programs' time
    assert sum(s.seconds.values()) == pytest.approx(s.programs_s)
    err = capsys.readouterr().err
    assert "zoo_moe_experts" in err and "padding 5.000 %" in err


def test_an_operation_the_compiler_named_takes_its_readers_part():
    """The TPU compiler's own operations (a ``ragged_dot`` made a Mosaic
    kernel named ``ragged-dot-none``; a memory move with no metadata)
    take the part of the first later operation that reads their result,
    through a chain of such operations; one whose readers name it not
    (a multi-output fusion, read through its tuple's elements) takes
    its first operand's part; what neither reaches stays unnamed, and
    so does what the program itself left unnamed."""
    assert admit_parts.instruction(
        "%multiply.2 = f32[8]{0} multiply(f32[8]{0} %a, f32[8]{0} %b.1), "
        "metadata={op_name=\"x\"}") == ("%multiply.2", ("%a", "%b.1"))
    assert admit_parts.instruction(
        "%fusion.3 = (f32[8], f32[8]) fusion(%copy-done, %w), kind=kLoop, "
        "calls=%fused_computation.3") == ("%fusion.3", ("%copy-done", "%w"))
    experts = ADMIT + "zoo_moe/zoo_moe_experts/"
    ops = [
        ("%ragged-dot-none = f32[8] custom-call(%fusion.1), "
         'custom_call_target="tpu_custom_call"', "ragged-dot-none"),
        ("%copy-start = (f32[8], u32[]) copy-start(%param.4)", ""),
        ("%copy-done = f32[8] copy-done(%copy-start)", ""),
        ("%fusion.3 = f32[8] fusion(%copy-done, %ragged-dot-none), "
         "kind=kLoop, calls=%fused_computation.3", experts + "convert"),
        ("%copy.7 = f32[8] copy(%fusion.3)", ""),
        ("%fusion.8 = f32[8] fusion(%fusion.3), kind=kLoop", ADMIT + "add"),
        ("%ragged-dot-none = f32[8] custom-call(%fusion.8)",
         "ragged-dot-none"),
        ("%fusion.9 = f32[8] fusion(%ragged-dot-none), kind=kLoop",
         ADMIT + "zoo_head/dot"),
        ("%fusion.10 = (f32[8], f32[8]) fusion(%param.2, %fusion.9), "
         "kind=kLoop", ""),
        ("%fusion.11 = f32[8] fusion(%get-tuple-element.3), kind=kLoop",
         ADMIT + "add"),
        ("%copy-done.9 = f32[8] copy-done(%copy-start.9)", ""),
    ]
    names = ("zoo_moe_experts", "zoo_head")
    assert admit_parts.program_parts(ops, names) == [
        "zoo_moe_experts", "zoo_moe_experts", "zoo_moe_experts",
        "zoo_moe_experts", "zoo_moe_experts", admit_parts.UNNAMED,
        "zoo_head", "zoo_head", "zoo_head", admit_parts.UNNAMED,
        admit_parts.UNNAMED]
    # in a trace: the compiler's kernel counts with the experts
    tagged = rows()
    at = next(i for i, r in enumerate(tagged) if r[3] == 40 * MS)
    tagged[at] = tagged[at][:2] + (
        "%fusion.4 = f32[8] fusion(%ragged-dot-none), kind=kLoop",) \
        + tagged[at][3:]
    tagged.insert(at, (DEV, "XLA Ops", "%ragged-dot-none = f32[8] "
                       "custom-call(%fusion.3)", 39.5 * MS, 40 * MS,
                       "ragged-dot-none"))
    s = admit_parts.split(ctx_of(program_spans.build(tagged)))
    assert s.seconds["zoo_moe_experts"] == pytest.approx(15.5e-3)
    assert s.seconds["zoo_attn_proj"] == pytest.approx(9.5e-3)
    assert sum(s.seconds.values()) == pytest.approx(s.programs_s)


def test_the_readers_on_the_synthetic_trace():
    ctx = ctx_of(program_spans.build(rows()))
    cfg = ctx["config"]
    c = c2.dims(cfg)
    assert read("admit_unnamed_share", ctx) == pytest.approx(100 * 5 / 60)
    pairs = (c["full"] * c2.visible_keys(4864)
             + c["sliding"] * c2.visible_keys(4864, c["window"]))
    assert read("admit_attention_mxu_roofline", ctx) == pytest.approx(
        100 * 4 * c["h"] * c["hd"] * pairs / 197e12 / 10e-3)
    expert = c2.expert_params(cfg)
    floor = max(2 * c2.held_pairs_per_token(cfg) * expert * c["layers"]
                * 4864 / 197e12,
                2 * c["held"] * expert * c["layers"] / 819e9)
    assert read("admit_experts_mxu_roofline", ctx) == pytest.approx(
        100 * floor / 15e-3)
    dense = (2 * 4864 * c["layers"] * (c2.attention_params(cfg)
                                       + c["d"] * c["published"]
                                       + c["shared"] * expert)
             + 2 * c["d"] * c["vocab"])
    assert read("admit_dense_mxu_roofline", ctx) == pytest.approx(
        100 * dense / 197e12 / 26e-3)


@pytest.mark.parametrize("metric", NEW)
def test_without_the_names_the_readers_read_nothing(metric, monkeypatch):
    """A trace whose admit programs hold no part (compiled without the
    names), a program without ``ADMIT_PARTS`` (the parent of the change
    that brought them), and no trace at all."""
    bare = program_spans.build(rows(admit="jit(admit)/zoo_prefill/"))
    for i, r in enumerate(bare.ops):
        bare.ops[i] = r[:4] + (r[4].replace("zoo_", "x_"),)
    assert read(metric, ctx_of(bare)) is None
    assert read(metric, ctx_of(None)) is None
    monkeypatch.setattr(admit_parts, "part_names", lambda: None)
    assert read(metric, ctx_of(program_spans.build(rows()))) is None


def test_the_families_floors_at_the_cells_sizes():
    """Each floor by family, at a prompt of 512: gpt2-medium's dense
    matmuls are 2 x 512 x 302 M weights and its head 2 x 51.5 M;
    granite's attention is its 4 layers' causal pairs."""
    gpt2 = harness.resolve("gpt2m-chat-closed")
    d, ff, vocab = 1024, 4096, 50257
    assert admit_parts.dense_flops(gpt2, 512) == (
        2 * 512 * 24 * (4 * d * d + 2 * d * ff) + 2 * d * vocab)
    assert admit_parts.attention_flops(gpt2, 512) == \
        24 * 2 * d * 512 * 513
    granite = harness.resolve("granite4h-chat-closed-64")
    assert admit_parts.attention_flops(granite, 512) == \
        4 * 4 * 32 * 64 * 512 * 513 // 2
