"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside jax and compiles for a chip that is
DESCRIBED, not attached.  Interpret-mode tests cannot see what it
refuses — a block that breaks the tiling rule, a kernel past the scoped
VMEM limit, a Mosaic call GSPMD will not partition — and each of those
stopped the program on first contact with a v5e.  These cases hold the
flash kernels (forward, dq, dkv) and the decode-attention kernel to the
compiler at the shapes the main paths send them, so a later change is
refused here at no chip time.

Nothing runs (there is no device), so nothing here says anything about
results or speed.

The topology is described inside a module-scoped fixture and only there:
one process at a time may load the TPU library, so describing it while a
module is imported would break collection under several workers.
"""

import importlib
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# ops/__init__ re-exports a function named ``attention``: import the module
A = importlib.import_module("analytics_zoo_tpu.ops.attention")


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2, with the persistent compilation cache off
    around its users: a compile for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernels(sharding, b, h, sq, sk, d, dtype, causal, masked,
                     which=("fwd", "dq", "dkv")):
    """Compile the named kernels of ``flash_attention`` at its own block
    choice; returns how many Mosaic calls each program holds."""
    q = jax.ShapeDtypeStruct((b, h, sq, d), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, h, sk, d), dtype, sharding=sharding)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=sharding)

    def fwd(q, k, v, lens):
        return A.flash_attention(q, k, v, causal=causal, layout="bhsd",
                                 kv_lengths=lens if masked else None)

    def loss(q, k, v, lens):
        return jnp.sum(fwd(q, k, v, lens).astype(jnp.float32))

    programs = {"fwd": fwd, "dq": jax.grad(loss, argnums=0),
                "dkv": jax.grad(loss, argnums=(1, 2))}
    calls = {}
    for name in which:
        text = jax.jit(programs[name]).lower(q, kv, kv, lens) \
            .compile().as_text()
        calls[name] = text.count("tpu_custom_call")
    return calls


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("shape", [(8, 12, 2048, 64), (1, 12, 8192, 64)])
def test_flash_kernels_compile_at_main_path_shapes(one_chip, shape, masked):
    """The LM train step's shape (chip_smoke.py) and a long-context one:
    bf16, causal, with and without the key-length mask."""
    b, h, s, d = shape
    calls = _compile_kernels(one_chip, b, h, s, s, d, jnp.bfloat16,
                             causal=True, masked=masked)
    # the backward programs replay the forward kernel for its residuals
    assert calls == {"fwd": 1, "dq": 2, "dkv": 2}


def test_compiled_step_names_the_three_kernels(one_chip):
    """The names a device profile goes by survive the chip's compiler:
    the custom VJP at the benchmark's train shape (4 x 16 heads x 1024 x
    64, bf16, causal) compiles to custom calls that carry them."""
    from analytics_zoo_tpu.observability import profile
    x = jax.ShapeDtypeStruct((4, 16, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, causal=True,
                                         layout="bhsd")
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in (profile.KERNEL_FLASH_FWD, profile.KERNEL_FLASH_BWD_DQ,
                   profile.KERNEL_FLASH_BWD_DKV):
        assert text.count(kernel) >= 1, kernel


def test_prompt_bucket_prefill_compiles(one_chip):
    """The decode engine's admit plan prefills a (1, heads, bucket, d)
    f32 prompt; bucket 48 is not a multiple of 128, so it pads."""
    assert A._flash_plan(True, 48, 48, 64, jnp.float32)[0] == \
        (128, 128, 80, 80)
    calls = _compile_kernels(one_chip, 1, 12, 48, 48, 64, jnp.float32,
                             causal=True, masked=False, which=("fwd",))
    assert calls == {"fwd": 1}


@pytest.mark.parametrize("bucket,plan", [
    (128, (128, 128, 0, 0)), (256, (256, 256, 0, 0)),
    (512, (512, 512, 0, 0)), (768, (384, 384, 0, 0)),
])
def test_chat_cell_prefill_buckets_compile(one_chip, bucket, plan):
    """``gpt2m-chat-closed``'s admit plans prefill (1, 16 heads, bucket,
    64) float32 prompts, causal, forward only: one custom call, under
    the forward kernel's name."""
    from analytics_zoo_tpu.observability import profile
    assert A._flash_plan(True, bucket, bucket, 64, jnp.float32)[0] == plan
    x = jax.ShapeDtypeStruct((1, 16, bucket, 64), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(lambda q, k, v: A.flash_attention(
        q, k, v, causal=True, layout="bhsd")).lower(x, x, x) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert profile.KERNEL_FLASH_FWD in text


@pytest.mark.parametrize("s,plan", [
    (320, (384, 384, 64, 64)),     # no 128-multiple divisor: pad to 384
    (640, (128, 128, 0, 0)),       # 5 x 128: no larger block divides it
    (1000, (512, 512, 24, 24)),    # pad to 1024
])
def test_dispatcher_choice_compiles_for_awkward_lengths(one_chip, s, plan):
    """What ``auto`` sends to the kernel must compile: the eligibility
    predicate and the block choice are the dispatcher's own.  (All three
    were refused by this compiler under the old ``block >= 8`` rule.)"""
    assert A._flash_supports(True, s, s, 64, jnp.bfloat16)
    assert A._flash_plan(True, s, s, 64, jnp.bfloat16)[0] == plan
    calls = _compile_kernels(one_chip, 2, 12, s, s, 64, jnp.bfloat16,
                             causal=True, masked=False)
    assert calls == {"fwd": 1, "dq": 2, "dkv": 2}


def test_vmem_bound_is_where_the_compiler_put_it(one_chip):
    """Whole-K/V blocking caps the sequence: past the stated bound the
    predicate says no (the compiler refused s=32768 d=64 and s=16384
    d=128 with 'exceeded scoped vmem limit'), and the largest shape it
    admits still compiles."""
    assert not A._flash_supports(True, 32768, 32768, 64, jnp.bfloat16)
    assert not A._flash_supports(True, 16384, 16384, 128, jnp.bfloat16)
    assert not A._flash_supports(True, 8192, 8192, 128, jnp.float32)
    assert not A._flash_supports(False, 128, 12416, 64, jnp.bfloat16)
    with pytest.raises(ValueError, match="VMEM"):
        A.flash_attention(*(jnp.zeros((1, 1, 32768, 64), jnp.bfloat16),)
                          * 3, layout="bhsd", interpret=True)
    assert A._flash_supports(True, 12288, 12288, 64, jnp.bfloat16)
    calls = _compile_kernels(one_chip, 1, 12, 12288, 12288, 64,
                             jnp.bfloat16, causal=True, masked=True)
    assert calls == {"fwd": 1, "dq": 2, "dkv": 2}


def test_flash_under_a_four_chip_mesh_compiles(topo, monkeypatch):
    """Under a Trainer's {data: 2, fsdp: 2} mesh the kernel must sit in
    a shard_map: handed to GSPMD bare, this compiler answers 'Mosaic
    kernels cannot be automatically partitioned'."""
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    # the described chip is a TPU; jax.devices() here still says cpu
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "fsdp"))
    sharded = NamedSharding(mesh, P(("data", "fsdp")))
    q = jax.ShapeDtypeStruct((8, 12, 2048, 64), jnp.bfloat16,
                             sharding=sharded)

    def loss(q, k, v):
        return jnp.sum(A.attention_bhsd(q, k, v, causal=True)
                       .astype(jnp.float32))

    with mesh_lib.active_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
            .lower(q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") == 3


# ------------------------------------------------- the decode step's op
HEADS, D_HEAD, MAX_LEN = 16, 64, 1024     # GPT-2 medium's, as published
SLAB_BYTES = MAX_LEN * HEADS * D_HEAD * 4     # one slot of one slab


@pytest.fixture
def on_the_chip(monkeypatch):
    """The described chip is a TPU; ``jax.devices()`` here says cpu."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)


def _op_args(slots, sharding_of):
    """ShapeDtypeStructs of ``decode_attention``'s operands;
    ``sharding_of(rank)`` places each."""
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sharding_of(len(shape)))
    row, slab = (slots, HEADS * D_HEAD), A.kv_slab_shape(
        slots, MAX_LEN, HEADS, D_HEAD)
    return (spec(row), spec(row), spec(row), spec(slab), spec(slab),
            spec((slots,), jnp.int32))


@pytest.mark.parametrize("slots", [16, 32])
def test_decode_attention_compiles_in_place(one_chip, on_the_chip, slots):
    """The op at the benchmark's widths: one Mosaic call that carries
    its name, the slabs aliased through it, and no temporary at all the
    size of a slot's slab (a copy of an operand would be ``slots`` of
    them)."""
    from analytics_zoo_tpu.observability import profile

    def op(q, k, v, ck, cv, pos):
        return A.decode_attention(q, k, v, ck, cv, pos, HEADS)

    compiled = jax.jit(op, donate_argnums=(3, 4)).lower(
        *_op_args(slots, lambda rank: one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert profile.KERNEL_DECODE_ATTN in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < SLAB_BYTES
    assert mem.alias_size_in_bytes == 2 * slots * SLAB_BYTES


def test_decode_attention_compiles_under_a_four_chip_mesh(topo,
                                                          on_the_chip):
    """A mesh-sharded engine splits the slots over its devices: handed
    to GSPMD bare the kernel is refused ('Mosaic kernels cannot be
    automatically partitioned'), so it sits in a shard_map."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "tensor"))
    axes = ("data", "tensor")

    def sharded(rank):
        return NamedSharding(mesh, P(axes, *([None] * (rank - 1))))

    def op(q, k, v, ck, cv, pos):
        return A.decode_attention(q, k, v, ck, cv, pos, HEADS, mesh=mesh)

    compiled = jax.jit(op, donate_argnums=(3, 4)).lower(
        *_op_args(16, sharded)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # each device holds, and aliases, its own four slots
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * 4 * SLAB_BYTES


@pytest.fixture(scope="module")
def two_layers():
    """Two layers at GPT-2 medium's widths (the vocabulary is not: it
    has no part in the slabs)."""
    from analytics_zoo_tpu.models import TransformerLM
    lm = TransformerLM(vocab_size=512, seq_len=MAX_LEN, n_layers=2,
                       d_model=HEADS * D_HEAD, n_heads=HEADS)
    lm.ensure_inference_ready()
    return lm


def _engine_without_state(hyper, slots, device, weights, max_len=MAX_LEN):
    """A ``DecodeEngine`` at ``slots`` x 1024 made without its state (a
    described device holds no array), and the dict its ``_plan`` fills:
    each plan it is asked for is lowered over the ``weights`` shapes
    under its name, and no further."""
    from analytics_zoo_tpu.models.generation import family_of
    from analytics_zoo_tpu.pipeline.inference.decode import DecodeEngine
    eng = object.__new__(DecodeEngine)
    eng.capacity, eng.max_len = slots, max_len
    eng._hyper, eng._n_layers = dict(hyper), int(hyper["n_layers"])
    eng._fam = family_of(hyper)
    eng._slab_dtype = eng._fam.slab_dtype(weights[0])
    eng._draft_hyper = eng._mesh = None
    eng._device = device
    eng._admit_fns = {}
    lowered = {}
    eng._plan = lambda name, jitted, specs: lowered.__setitem__(
        name, jitted.lower(*specs, weights))
    return eng, lowered


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


_STEPK = {}


@pytest.fixture
def compiled_stepk(topo, one_chip, on_the_chip, two_layers):
    """``jit_stepk`` (4 steps) as the engine builds it, compiled for the
    described chip at ``slots`` x 1024, once a module."""
    def compiled(slots):
        if slots not in _STEPK:
            eng, lowered = _engine_without_state(
                two_layers.hyper, slots, topo.devices[0], _abstract(
                    (two_layers.trainer.state.params, None), one_chip))
            eng._build_stepk_plan(4)
            [(name, plan)] = lowered.items()
            assert name == "step4"
            _STEPK[slots] = plan.compile()
        return _STEPK[slots]
    return compiled


@pytest.mark.parametrize("slots", [16, 32])
def test_fused_step_plan_compiles_without_a_slab_sized_temporary(
        compiled_stepk, slots):
    """At 16 x 1024 (the benchmark's cell) and at 32 x 1024, which the
    chip's compiler refused before the slab was lane-dense ('Used 20.21G
    of 15.75G hbm': a padded copy of every layer's keys and values)."""
    from analytics_zoo_tpu.observability import profile
    compiled = compiled_stepk(slots)
    text = compiled.as_text()
    assert f"HloModule {profile.PROGRAM_STEPK}" in text
    assert profile.KERNEL_DECODE_ATTN in text
    assert text.count("tpu_custom_call") == 2       # one a layer
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < slots * SLAB_BYTES   # one layer's slab
    assert mem.alias_size_in_bytes >= 4 * slots * SLAB_BYTES
    # no operation but the kernel takes or makes a whole slab
    slab = f"f32[{slots},{MAX_LEN},{HEADS * D_HEAD}]"
    for line in text.splitlines():
        if slab in line.split(" = ", 1)[-1].split("(", 1)[0] \
                and " fusion(" in line:
            pytest.fail("a fusion makes a slab: " + line[:200])


def _computations(hlo_text):
    """{computation name: its instruction lines} of a compiled module's
    text."""
    import re
    out, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def test_fused_step_plan_sorts_in_one_branch_of_a_conditional(
        compiled_stepk):
    """The compiler kept the pick's ``lax.cond`` a ``conditional`` (it
    did not turn it into a select that runs both sides): the only sort
    of the step sits in one of its branch computations, under the scope
    ``zoo_sample``, and the other branch holds none."""
    import re
    from analytics_zoo_tpu.observability import profile
    comps = _computations(compiled_stepk(16).as_text())
    sorts = [(name, line) for name, lines in comps.items()
             for line in lines if re.search(r" sort\(", line)]
    [(home, sort)] = sorts
    assert f"jit({profile.SCOPE_SAMPLE})/cond/branch_1_fun" in sort
    [cond] = [line for lines in comps.values() for line in lines
              if " conditional(" in line]
    assert f"jit({profile.SCOPE_SAMPLE})/cond" in cond
    branches = re.search(r"branch_computations=\{([^}]*)\}", cond)
    argmax, sorted_ = [b.strip().lstrip("%")
                       for b in branches.group(1).split(",")]
    assert home == sorted_
    assert not any("cumsum" in line or "top_k" in line
                   for line in comps[argmax])


# ------------------------------------- the benchmark's cells, whole model
def _gpt2_medium():
    """The benchmark's configuration at its published depth and widths
    (read from its file), as ``benchmark/adapters/gpt2.py`` builds it,
    and its parameters and model state as shapes: nothing is
    allocated."""
    import json
    from analytics_zoo_tpu.models import TransformerLM
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "gpt2-medium.json")) as f:
        cfg = json.load(f)
    lm = TransformerLM(
        vocab_size=cfg["vocab_size"], seq_len=cfg["n_positions"],
        max_len=cfg["n_positions"], n_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], n_heads=cfg["n_head"],
        d_ff=4 * cfg["n_embd"], dropout=cfg["resid_pdrop"])
    assert (lm.hyper["n_layers"], lm.hyper["d_model"], lm.hyper["n_heads"],
            lm.hyper["d_ff"], lm.hyper["vocab_size"]) \
        == (24, HEADS * D_HEAD, HEADS, 4096, 50257)
    params, model_state = jax.eval_shape(
        lambda key: lm.to_graph().init(key), jax.random.PRNGKey(0))
    # 406.2 M: the published 354.8 M and an untied head with its bias
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(params)) == 406_238_289
    return lm, params, model_state


def _module_name(lowered):
    return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]


def test_pretrain_cell_train_step_lowers_at_published_widths(
        one_chip, on_the_chip):
    """``gpt2m-pretrain-1k``'s step, whole: 24 layers, 4 microbatches of
    4 x 1024 tokens scanned in one program, bf16 compute over f32 master
    weights and Adam, the flash kernels lowered for the described chip.
    Only that it lowers, and under the name a profile goes by."""
    from analytics_zoo_tpu.observability import profile
    from analytics_zoo_tpu.train.trainer import build_train_step
    lm, params, model_state = _gpt2_medium()
    lm.compile("adam", "class_nll", seed=0, compute_dtype=jnp.bfloat16)
    tr = lm.trainer
    opt_state = jax.eval_shape(tr.optimizer.init, params)
    rows = jax.ShapeDtypeStruct((4, 4, MAX_LEN), jnp.int32)
    operands = _abstract((params, model_state, opt_state,
                          jax.random.PRNGKey(0), rows, rows), one_chip)
    step = build_train_step(tr.model, tr.loss_fn, tr.optimizer,
                            compute_dtype=jnp.bfloat16, accum_steps=4)
    lowered = step.lower(*operands)
    assert _module_name(lowered) == profile.PROGRAM_TRAIN_STEP
    text = lowered.as_text()
    for kernel in (profile.KERNEL_FLASH_FWD, profile.KERNEL_FLASH_BWD_DQ,
                   profile.KERNEL_FLASH_BWD_DKV):
        assert kernel in text, kernel


def test_chat_cell_plans_lower_at_published_widths(topo, one_chip,
                                                   on_the_chip):
    """``gpt2m-chat-closed``'s engine, whole: 24 layers, 16 slots x 1024,
    the admit plan of its 128-token bucket and the fused window of 4
    steps.  Only that they lower, and under the names a profile goes
    by."""
    from analytics_zoo_tpu.observability import profile
    lm, params, _ = _gpt2_medium()
    eng, lowered = _engine_without_state(
        lm.hyper, 16, topo.devices[0], _abstract((params, None), one_chip))
    eng._admit_fn_for(128)
    eng._build_stepk_plan(4)
    assert {name: _module_name(plan) for name, plan in lowered.items()} \
        == {"admit128": profile.PROGRAM_ADMIT,
            "step4": profile.PROGRAM_STEPK}
    # the two pinned plans hold exactly the kernels they held before a
    # second family came (ISSUE 36): one decode kernel a layer in the
    # window, one flash forward a layer in the admission, nothing of the
    # other family's
    step, admit = (lowered[n].as_text() for n in ("step4", "admit128"))
    assert step.count(profile.KERNEL_DECODE_ATTN) > 0
    assert profile.KERNEL_FLASH_FWD in admit
    assert profile.KERNEL_DECODE_ATTN not in admit
    for text in (step, admit):
        assert profile.KERNEL_DECODE_ATTN_GQA not in text
        assert profile.SCOPE_MOE not in text
        assert "ragged" not in text


def _command_a_plus():
    """The benchmark's ``command-a-plus-ep16`` at its published widths
    (4 layers, 8 of 128 experts held, an eighth of the vocabulary), as
    ``benchmark/adapters/cohere2moe.py`` builds it, with the reference's
    bfloat16 parameter shapes: nothing is allocated."""
    import json
    from benchmark.adapters import cohere2moe as adapter
    from benchmark.reference import cohere2moe as ref
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "command-a-plus-ep16.json")) as f:
        cfg = json.load(f)
    lm = adapter.build(cfg, {})
    params = {layer: {leaf: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                      for leaf, (shape, _) in leaves.items()}
              for layer, leaves in ref.param_spec(cfg).items()}
    # the keras graph's own parameter tree has the same leaves
    graph, _ = jax.eval_shape(lambda key: lm.to_graph().init(key),
                              jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, graph) \
        == jax.tree_util.tree_map(lambda a: a.shape, params)
    # 3,122.7 M parameters: 6.25 GB at 2 bytes
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(params)) == 3_122_679_808
    return lm, params


def test_docqa_cell_plans_lower_at_published_widths(topo, one_chip,
                                                    on_the_chip):
    """``cmdaplus-docqa-closed-5k``'s engine, whole: 4 layers, 48 slots x
    6144 (three rings of 4096 rows and one slab of 6144), the admit plan
    of its 4608-token bucket and the fused window of 4 steps, lowered
    for the described chip from shapes alone: the window holds the
    grouped decode kernel and the expert sublayer's scopes, the
    admission the flash forward and the grouped products."""
    from analytics_zoo_tpu.observability import profile
    lm, params = _command_a_plus()
    eng, lowered = _engine_without_state(
        lm.hyper, 48, topo.devices[0], _abstract((params, None), one_chip),
        max_len=6144)
    assert [keys[0][1] for keys, _ in eng._layer_state_shapes()] \
        == [4096] * 3 + [6144]
    eng._admit_fn_for(4608)
    eng._build_stepk_plan(4)
    assert {name: _module_name(plan) for name, plan in lowered.items()} \
        == {"admit4608": profile.PROGRAM_ADMIT,
            "step4": profile.PROGRAM_STEPK}
    step, admit = (lowered[n].as_text(debug_info=True)
                   for n in ("step4", "admit4608"))
    assert profile.KERNEL_DECODE_ATTN_GQA in step
    assert profile.KERNEL_FLASH_FWD in admit
    assert "ragged_dot" in admit and "ragged_dot" not in step
    for text in (step, admit):
        for scope in (profile.SCOPE_MOE_ROUTER, profile.SCOPE_MOE_EXPERTS,
                      profile.SCOPE_MOE_SHARED):
            assert scope in text, scope
    # the slabs are bfloat16 and the three rings are the window's length
    assert "48x4096x1024xbf16" in step and "48x6144x1024xbf16" in step


# ------------------------------ granite4h-chat-closed-64: state-space layers
GRANITE_SLOTS, GRANITE_LEN = 64, 1280
#: one layer's float32 state over the cell's slots: 64 heads x 64 x 128
GRANITE_STATE_BYTES = GRANITE_SLOTS * 64 * 64 * 128 * 4


def _granite(layer_types=None):
    """The benchmark's ``granite-4.0-h-micro`` at its published widths, as
    ``benchmark/adapters/granitehybrid.py`` builds it (``layer_types``:
    fewer layers of the same widths), with the reference's bfloat16
    parameter shapes: nothing is allocated."""
    import json
    from benchmark.adapters import granitehybrid as adapter
    from benchmark.reference import granitehybrid as ref
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    if layer_types is not None:
        cfg.update(layer_types=layer_types,
                   num_hidden_layers=len(layer_types))
    lm = adapter.build(cfg, {})
    params = {layer: {leaf: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                      for leaf, (shape, _) in leaves.items()}
              for layer, leaves in ref.param_spec(cfg).items()}
    return lm, params, ref.n_params(cfg)


@pytest.fixture
def ssm_on_the_chip(monkeypatch):
    """The described chip is a TPU for the state-space ops too."""
    from analytics_zoo_tpu.ops import ssm
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)


def test_granite_cell_plans_lower_at_published_widths(topo, one_chip,
                                                      ssm_on_the_chip):
    """``granite4h-chat-closed-64``'s engine, whole: 40 layers, 64 slots x
    1280, the admit plan of its 1024-token bucket and the fused window of
    4 steps, lowered from shapes alone: the window holds the state-update
    kernel once a Mamba layer and the mixers' scopes, the admission the
    flash forward once an attention layer and the chunked scan."""
    from analytics_zoo_tpu.observability import profile
    lm, params, n = _granite()
    assert n == 3_191_396_096
    eng, lowered = _engine_without_state(
        lm.hyper, GRANITE_SLOTS, topo.devices[0],
        _abstract((params, None), one_chip), max_len=GRANITE_LEN)
    eng._admit_fn_for(1024)
    eng._build_stepk_plan(4)
    assert {name: _module_name(plan) for name, plan in lowered.items()} \
        == {"admit1024": profile.PROGRAM_ADMIT,
            "step4": profile.PROGRAM_STEPK}
    step, admit = (lowered[n].as_text(debug_info=True)
                   for n in ("step4", "admit1024"))
    # the kernel's jit is lowered once and called once a Mamba layer
    import re
    assert profile.KERNEL_SSM_DECODE in step
    assert len(re.findall(r"call @_ssm_decode_call\(", step)) == 36
    assert profile.KERNEL_SSM_DECODE not in admit
    assert profile.KERNEL_FLASH_FWD in admit
    # the grouped decode kernel takes the 64-wide heads, two cached heads
    # a lane tile: once an attention layer, and only in the window
    assert profile.KERNEL_DECODE_ATTN_GQA in step
    assert len(re.findall(r"call @_decode_gqa_call\(", step)) == 4
    assert profile.KERNEL_DECODE_ATTN_GQA not in admit
    for text in (step, admit):
        for scope in (profile.SCOPE_SSM, profile.SCOPE_SSM_CONV,
                      profile.SCOPE_SSM_SCAN):
            assert scope in text, scope
    # the state float32, the windows and slabs bfloat16
    assert "64x64x64x128xf32" in step and "64x3x4352xbf16" in step
    assert "64x1280x512xbf16" in step


def test_ssm_decode_compiles_in_place(one_chip, ssm_on_the_chip):
    """The state update at the cell's 64 slots: one Mosaic call that
    carries its name, the state aliased through it, and no temporary the
    size of a slot's state."""
    from analytics_zoo_tpu.observability import profile
    from analytics_zoo_tpu.ops import ssm

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(ssm.ssm_decode, donate_argnums=(0,)).lower(
        spec(GRANITE_SLOTS, 64, 64, 128), spec(GRANITE_SLOTS, 64, 64),
        spec(GRANITE_SLOTS, 64), spec(64), spec(GRANITE_SLOTS, 128),
        spec(GRANITE_SLOTS, 128), spec(64)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert profile.KERNEL_SSM_DECODE in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == GRANITE_STATE_BYTES
    assert mem.temp_size_in_bytes < GRANITE_STATE_BYTES // GRANITE_SLOTS


def test_granite_step_plan_compiles_without_a_state_sized_temporary(
        topo, one_chip, ssm_on_the_chip):
    """One Mamba layer and one attention layer at the published widths,
    the fused window of 4 steps at 64 slots x 1280: every slot's state
    updated in place (aliased), no temporary a layer's state in size, no
    operation but the grouped decode kernel making a layer's slab."""
    lm, params, _ = _granite(["mamba", "attention"])
    eng, lowered = _engine_without_state(
        lm.hyper, GRANITE_SLOTS, topo.devices[0],
        _abstract((params, None), one_chip), max_len=GRANITE_LEN)
    eng._build_stepk_plan(4)
    compiled = lowered["step4"].compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < GRANITE_STATE_BYTES
    assert mem.alias_size_in_bytes >= GRANITE_STATE_BYTES
    # nor a layer's slab in size (64 x 1280 x 512 x 2 B): no operation but
    # the kernel makes one (the masked full-length path copied each slab
    # to another layout for its per-head contraction, and scattered the
    # new row into it)
    import re
    slab = GRANITE_SLOTS * GRANITE_LEN * 8 * 64
    passed_on = {"parameter", "get-tuple-element", "bitcast", "custom-call"}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and m.group(2) not in passed_on \
                and np.prod([int(n) for n in m.group(1).split(",")]) == slab:
            pytest.fail("an operation makes a slab: " + line[:200])


# -------------------------------- ouro26-chat-closed-12: layers run in a loop
OURO_SLOTS, OURO_LEN, OURO_PASSES = 12, 384, 4
#: one layer's key (or value) slab over the cell's slots: every pass
OURO_SLAB = OURO_SLOTS * OURO_PASSES * OURO_LEN * 16 * 128


def _ouro(layers):
    """The benchmark's ``ouro-2.6b`` at its published widths and passes,
    ``layers`` of its 48 layers, as ``benchmark/adapters/ouro.py`` builds
    it, with the reference's bfloat16 parameter shapes: nothing is
    allocated."""
    import json
    from benchmark.adapters import ouro as adapter
    from benchmark.reference import ouro as ref
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    assert ref.n_params(cfg) == 2_667_974_657
    cfg.update(num_hidden_layers=layers)
    lm = adapter.build(cfg, {})
    params = {layer: {leaf: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                      for leaf, (shape, _) in leaves.items()}
              for layer, leaves in ref.param_spec(cfg).items()}
    return lm, params


def test_ouro_step_plan_runs_one_pass_program_in_a_loop(topo, one_chip,
                                                        on_the_chip):
    """4 layers at the published widths, 4 passes, the fused window of 4
    steps at 12 slots x 384: the passes are a loop around ONE copy of the
    layers, so the grouped decode kernel is called once a layer (4 calls
    in the lowered program and in the compiled one, not passes x layers
    = 16); each layer's slabs, a pass axis inside a slot, are aliased
    through the window; and no operation but the kernel makes an array a
    layer's slab, or a pass's part of one, in size (a pass's part sliced
    out and written back would be one)."""
    import re
    from analytics_zoo_tpu.observability import profile
    lm, params = _ouro(4)
    eng, lowered = _engine_without_state(
        lm.hyper, OURO_SLOTS, topo.devices[0],
        _abstract((params, None), one_chip), max_len=OURO_LEN)
    assert [keys[0] for keys, _ in eng._layer_state_shapes()] \
        == [(OURO_SLOTS, OURO_PASSES, OURO_LEN, 16 * 128)] * 4
    eng._build_stepk_plan(4)
    step = lowered["step4"]
    assert _module_name(step) == profile.PROGRAM_STEPK
    assert len(re.findall(r"call @_decode_gqa_call\(",
                          step.as_text(debug_info=True))) == 4
    compiled = step.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    assert profile.KERNEL_DECODE_ATTN_GQA in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 4 * OURO_SLAB * 2
    passed_on = {"parameter", "get-tuple-element", "bitcast", "custom-call"}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and m.group(2) not in passed_on \
                and np.prod([int(n) for n in m.group(1).split(",")]) \
                in (OURO_SLAB, OURO_SLAB // OURO_PASSES):
            pytest.fail("an operation makes a slab: " + line[:200])


def test_ouro_admit_plan_runs_one_pass_program_in_a_loop(topo, one_chip,
                                                         on_the_chip):
    """The admission of the 256-token bucket, same 4 layers: one flash
    forward a layer in the loop's body (not one a layer and a pass), the
    prompt's rows of every pass laid into the slot."""
    import re
    from analytics_zoo_tpu.observability import profile
    lm, params = _ouro(4)
    eng, lowered = _engine_without_state(
        lm.hyper, OURO_SLOTS, topo.devices[0],
        _abstract((params, None), one_chip), max_len=OURO_LEN)
    eng._admit_fn_for(256)
    admit = lowered["admit256"]
    assert _module_name(admit) == profile.PROGRAM_ADMIT
    text = admit.as_text(debug_info=True)
    assert len(re.findall(r"call @_flash_fwd_call\(", text)) == 4
    assert profile.KERNEL_DECODE_ATTN_GQA not in text
    assert "stablehlo.while" in text
    assert f"1x{OURO_PASSES}x256x2048xbf16" in text


def _kernels_without_locations(text):
    """Lowered text with each Mosaic kernel's serialized body replaced by
    a digest of the kernel printed WITHOUT its source locations (the
    file and line of the Python that traced it, which any edit above the
    kernel moves)."""
    import base64
    import hashlib
    import re
    from jax.interpreters.mlir import ir
    from jaxlib.mosaic.python import tpu

    def body(m):
        with ir.Context() as ctx, ir.Location.unknown():
            ctx.allow_unregistered_dialects = True
            tpu.register_dialect(ctx)
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r"\\22body\\22: \\22([^\\]*)\\22", body, text)


#: sha256 of ``_kernels_without_locations`` of each plan below, as the
#: programs were before the grouped decode kernel took a pass axis: a
#: slab without one lowers exactly as it did
OTHER_FAMILIES_STEP_PLANS = {
    "cmdaplus.step1":
        "8ed1148eaa6d6a7a8d45480aab487b2098d4bf0e84fefd2f7dab8bce545f112e",
    "cmdaplus.step4":
        "125072efc6acbdb3c88e8e4616d7c87343188882e6b1fe84803761e954d6bf22",
    "granite.step1":
        "bf89af6405081d072d5e084105ef9b5db46028f77ecc0612336fa67fe4a85237",
    "granite.step4":
        "5f4cbe420e07aba216222ac49135476e1e625aa84fec3eb391735ae77713fc56",
}


def test_the_other_families_step_plans_lower_as_before(topo, one_chip,
                                                       ssm_on_the_chip):
    """``cohere2_moe`` (4 layers at the published widths, 8 slots x 6144)
    and ``granitemoehybrid`` (a Mamba layer and an attention layer, 8
    slots x 1280): the single step and the fused window of 4, lowered
    for the described chip, are the programs they were, kernels and
    all."""
    import hashlib
    got = {}
    for family, (lm, params), max_len in (
            ("cmdaplus", _command_a_plus(), 6144),
            ("granite", _granite(["mamba", "attention"])[:2], GRANITE_LEN)):
        eng, lowered = _engine_without_state(
            lm.hyper, 8, topo.devices[0],
            _abstract((params, None), one_chip), max_len=max_len)
        eng._build_stepk_plan(4)
        eng._build_step_plan()
        for name, plan in lowered.items():
            got[f"{family}.{name}"] = hashlib.sha256(
                _kernels_without_locations(plan.as_text()).encode()
            ).hexdigest()
    assert got == OTHER_FAMILIES_STEP_PLANS
