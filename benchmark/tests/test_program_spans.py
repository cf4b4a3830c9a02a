"""``program_spans.py`` and the readers that go by the program's names,
on a small fixture written in ``trace_events``' own rows
(``spans_fixture.json``: times in ns, a window of 100 ms, one decode
dispatcher thread, one fused window and the three flash kernels on the
device), on a hand-made ``.xplane.pb`` for the scope reader, and on a
trace recorded here for the host spans' stats."""

import json
import os

import pytest

from benchmark import costs, program_spans, run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6        # ns
DISPATCHER = 3


def fixture_rows():
    with open(os.path.join(HERE, "spans_fixture.json")) as f:
        return [tuple(r) for r in json.load(f)]


def without_names(rows):
    """The same run as the parent of PR 26 would trace it: no ``zoo/``
    span, no kernel name, no scope."""
    out = []
    for plane, line, name, lo, hi, extra in rows:
        if name.startswith(program_spans.SPAN_PREFIX):
            continue
        if "tpu_custom_call" in name:
            name = "%custom-call.7 = " + name.split(" = ", 1)[1]
        out.append((plane, line, name, lo, hi,
                    {} if isinstance(extra, dict) else ""))
    return out


@pytest.fixture(scope="module")
def spans():
    return program_spans.build(fixture_rows())


def ctx_of(spans, cell, **more):
    return {**harness.resolve(cell), "program_spans": spans,
            "peaks": costs.peaks("TPU v5 lite"), "chips": 1,
            "counters": {}, "measured": {}, **more}


def read(metric, ctx):
    return harness.load_module("layer_metrics", metric).read(ctx)


# ------------------------------------------------------------ the module
def test_everything_is_clipped_to_the_traced_window(spans):
    assert (spans.lo, spans.hi) == (1 * MS, 101 * MS)
    assert spans.window_s == pytest.approx(0.1)
    # the operation before the window is gone, the one across its end cut
    assert [e for e in spans.ops if e[1].startswith("%fusion.0")] == []
    [last] = [e for e in spans.ops if e[1].startswith("%fusion.9")]
    assert last[2:4] == (100 * MS, 101 * MS)
    # the admission that began before the window is cut to it
    first = min(spans.named("decode/admit"), key=lambda e: e[2])
    assert first[2:4] == (1 * MS, 3 * MS)
    assert first[4]["queue_wait_us"] == 6000
    assert len(spans.modules) == 1 and spans.devices() == ["/device:TPU:0"]
    assert program_spans.build(
        [r for r in fixture_rows() if r[2] != "bench/traced"]) is None


def test_self_time_is_a_spans_time_less_its_childrens(spans):
    assert spans.thread_of("decode/") == DISPATCHER
    assert spans.thread_of("train/") is None
    own = {k: round(v * 1e3, 6) for k, v in
           spans.self_seconds(DISPATCHER).items()}
    # admit: 2 ms (cut) + 40 ms less the 30 ms of its admit_fetch
    assert own == {"zoo/decode/admit": 12.0, "zoo/decode/admit_fetch": 30.0,
                   "zoo/decode/dispatch": 2.0, "zoo/decode/fetch": 32.0,
                   "zoo/decode/fanout": 2.0}
    pieces = [(lo / MS, hi / MS, name.rsplit("/", 1)[1])
              for lo, hi, name in spans.innermost(DISPATCHER)]
    assert pieces == [(1, 3, "admit"), (5, 10, "admit"),
                      (10, 40, "admit_fetch"), (40, 45, "admit"),
                      (46, 48, "dispatch"), (48, 80, "fetch"),
                      (80, 82, "fanout")]


def test_kernels_and_scopes_on_the_device(spans):
    assert spans.kernel_seconds("zoo_flash_fwd") == (pytest.approx(2e-3), 1)
    assert spans.kernel_seconds("zoo_flash_bwd_dq") \
        == (pytest.approx(1e-3), 1)
    assert spans.kernel_seconds("zoo_flash_bwd_dkv") \
        == (pytest.approx(4e-3), 1)
    assert spans.kernel_seconds("zoo_flash") == (pytest.approx(7e-3), 3)
    # the sort (2 ms) + the call (4 ms, its own child inside it counted
    # once, the 0.5 ms of another scope's operation inside it taken off)
    assert spans.scope_seconds("zoo_sample") == pytest.approx(5.5e-3)
    assert spans.scope_seconds("zoo_decode_attention") \
        == pytest.approx(3e-3)
    assert spans.scope_seconds("zoo_prefill") == 0.0
    busy, merged = spans.busy()
    assert busy == pytest.approx(36e-3) and len(merged) == 5


def test_scope_names_are_matched_whole():
    path = "jit(train_step)/transpose(jvp(zoo_loss))/reduce_sum:"
    assert program_spans.in_scope(path, "zoo_loss")
    assert program_spans.in_scope("a/jit(zoo_sample)/top_k:", "zoo_sample")
    assert program_spans.in_scope("a/zoo_sample/top_k:", "zoo_sample")
    assert not program_spans.in_scope("a/not_zoo_sample/x:", "zoo_sample")
    assert not program_spans.in_scope("a/zoo_sample_2/x:", "zoo_sample")


def test_idle_seconds_go_to_the_most_specific_span(spans):
    idle = {k: round(v * 1e3, 6) for k, v in
            spans.idle_by_span(DISPATCHER).items()}
    assert idle == {"zoo/decode/admit": 4.0, "zoo/decode/admit_fetch": 10.0,
                    "zoo/decode/dispatch": 2.0, "zoo/decode/fetch": 27.0,
                    "zoo/decode/fanout": 2.0, "unattributed": 19.0}
    assert sum(idle.values()) == pytest.approx(100.0 - 36.0)


# ----------------------------------------------------------- the readers
def test_decode_readers_on_the_fixture(spans, capsys):
    ctx = ctx_of(spans, "gpt2m-chat-closed",
                 trace={"busy_s": 36e-3, "devices": 1})
    assert read("decode_queue_wait_ms", ctx) == pytest.approx(4.0)
    assert "2 admissions" in capsys.readouterr().err
    # only the admission that lies whole in the window
    assert read("decode_admit_block_ms", ctx) == pytest.approx(40.0)
    # all but fetch (32) and admit_fetch (30) of the 100 ms
    assert read("decode_loop_host_share", ctx) == pytest.approx(38.0)
    assert read("decode_sample_device_share", ctx) \
        == pytest.approx(100 * 5.5 / 36)
    assert read("serve_idle_attributed_share", ctx) \
        == pytest.approx(100 * 45 / 64)
    assert '"unattributed", 0.019' in capsys.readouterr().err


def test_flash_readers_split_the_triples_work(spans):
    ctx = ctx_of(spans, "gpt2m-pretrain-1k")
    t = ctx["workload"]["traffic"]
    micro, seq, d = t["batch"] // t["accum_steps"], t["seq_len"], 1024
    assert (micro, seq) == (4, 1024)
    flops = micro * costs.causal_attention_flops(seq, d)
    assert 3 * flops == costs.flash_flops(micro, seq, d)
    tensor = micro * seq * d * 2
    want = {}
    for kernel, tensors, ms in (("fwd", 4, 2.0), ("bwd_dq", 5, 1.0),
                                ("bwd_dkv", 6, 4.0)):
        floor_s = max(flops / 197e12, tensors * tensor / 819e9)
        want[kernel] = 100 * floor_s / (ms * 1e-3)
        assert read(f"flash_{kernel}_roofline", ctx) \
            == pytest.approx(want[kernel])
    # 43.6 us of flops bound the forward; bytes bound dq and dkv
    assert want["fwd"] == pytest.approx(2.182, abs=1e-3)
    assert want["bwd_dq"] == pytest.approx(5.121, abs=1e-3)
    assert want["bwd_dkv"] == pytest.approx(1.536, abs=1e-3)
    assert sum(program_spans.FLASH_TENSORS.values()) == 15


NEW = ["flash_fwd_roofline", "flash_bwd_dq_roofline",
       "flash_bwd_dkv_roofline", "decode_queue_wait_ms",
       "decode_admit_block_ms", "decode_loop_host_share",
       "decode_sample_device_share", "serve_idle_attributed_share"]


@pytest.mark.parametrize("metric", NEW)
def test_a_trace_without_the_names_reads_as_nothing(metric):
    """The parent of the PR that brought the names, and a run with no
    trace at all: every new reader returns ``None`` and raises
    nothing."""
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    [entry] = [m for m in manifest["per_layer"] if m["name"] == metric]
    [cell] = entry["workloads"]
    bare = program_spans.build(without_names(fixture_rows()))
    assert bare is not None and bare.host == [] and bare.ops
    trace = {"busy_s": 36e-3, "devices": 1}
    assert read(metric, ctx_of(bare, cell, trace=trace)) is None
    assert read(metric, ctx_of(None, cell, trace=trace)) is None


# --------------------------------------------------------- the file itself
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_scopes_are_read_from_the_event_metadata(tmp_path):
    """An ``XSpace`` written by hand in the wire format: one device
    plane whose two operations carry their scope as the ``tf_op`` stat
    of their METADATA, one by value and one by reference, some lines to
    step over, and a host plane to ignore."""
    def stat_meta(key, name):
        return field(5, field(1, key) + field(2, field(1, key)
                                              + field(2, name)))

    def event_meta(key, name, *stats):
        return field(4, field(1, key) + field(2, field(1, key) + field(
            2, name) + b"".join(field(5, s) for s in stats)))

    sort, scope = "%sort.7 = (f32[16,50257]) sort(...)", \
        "jit(stepk)/jit(zoo_sample)/vmap()/top_k:"
    device = (field(1, 1) + field(2, "/device:TPU:0")
              + field(3, field(2, "XLA Ops") + field(4, b"\x08\x01" * 50))
              + stat_meta(7, "tf_op") + stat_meta(8, "hlo_category")
              + stat_meta(9, "jit(stepk)/zoo_decode_mlp/dot_general:")
              + event_meta(1, sort, field(1, 8) + field(5, "sort"),
                           field(1, 7) + field(5, scope))
              + event_meta(2, "%fusion.1 = f32[8] fusion(...)",
                           field(1, 7) + field(7, 9))
              + event_meta(3, "%copy.2 = f32[8] copy(...)",
                           field(1, 8) + field(5, "copy")))
    host = field(1, 2) + field(2, "/host:CPU") + event_meta(
        1, "zoo/decode/admit", field(1, 7) + field(5, "not a scope"))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(field(1, device) + field(1, host))
    assert program_spans.op_scopes(str(path)) == {"/device:TPU:0": {
        sort: scope,
        "%fusion.1 = f32[8] fusion(...)":
            "jit(stepk)/zoo_decode_mlp/dot_general:"}}


def test_a_spans_counts_come_back_from_a_recorded_trace(tmp_path):
    """What ``TraceAnnotation`` is given as keyword arguments, and what
    ``set_metadata`` adds, is read back as the event's stats, through
    the harness's own tracer."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.observability import profile
    tracer = harness.Tracer(str(tmp_path), after_s=0.0)
    tracer.maybe_start(0.0)
    with profile.annotate("decode/admit", bucket=128, queue_wait_us=77):
        with profile.annotate("decode/admit_fetch"):
            jnp.ones((64, 64)).sum().block_until_ready()
    with profile.annotate("decode/fanout") as ann:
        ann.set_metadata(tokens=5, evicted=1)
    with jax.profiler.TraceAnnotation("bench/submit"):
        pass
    tracer.stop()
    spans = program_spans.parse(str(tmp_path))
    assert {e[1] for e in spans.host} == {
        "zoo/decode/admit", "zoo/decode/admit_fetch", "zoo/decode/fanout"}
    [admit] = spans.named("decode/admit")
    assert admit[4] == {"bucket": 128, "queue_wait_us": 77}
    assert spans.named("decode/fanout")[0][4] == {"tokens": 5, "evicted": 1}
    thread = spans.thread_of("decode/")
    own = spans.self_seconds(thread)
    [fetch] = spans.named("decode/admit_fetch")
    assert own["zoo/decode/admit"] == pytest.approx(
        (admit[3] - admit[2] - (fetch[3] - fetch[2])) / 1e9)
    assert spans.ops == []          # no device operation on the CPU
    ctx = ctx_of(spans, "gpt2m-chat-closed", trace=None)
    assert read("decode_queue_wait_ms", ctx) == pytest.approx(0.077)
    assert read("serve_idle_attributed_share", ctx) is None
    assert read("decode_sample_device_share", ctx) is None
