"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths, the trainer and the server, once each through
the entry points a user calls, at the full width of the models the repo
carries (GPT-2-small TransformerLM and ResNet-50; weights random, from a
seed), on ONE TPU chip in ONE process:

    python chip_smoke.py             # one chip: train-lm, serve-lm,
                                     # train-resnet50, serve-resnet50
    python chip_smoke.py --chips 4   # four chips: ONLY the cross-chip
                                     # paths and what they compare with

It is a smoke, not a benchmark: the seconds it prints are phase walls
with compilation inside, never rates.  It sets no platform, starts no
process, catches no phase's failure, and exits non-zero — printing no
result line — when jax finds no TPU.  The last line of stdout is the
one JSON object the driver reads.
"""

import argparse
import json
import sys
import time

import numpy as np

SEED = 0

# GPT-2-small widths at the published vocabulary (depth not cut)
LM = dict(vocab_size=50257, seq_len=2048, n_layers=12, d_model=768,
          n_heads=12, d_ff=3072)
LM_BATCH = 8           # the rehearsal's memory_analysis says this fits
LM_STEPS = 6
# per-step loss, fsdp on a {data: 2, fsdp: 2} mesh vs one device, bf16
# compute (the CPU record's bf16_rel was 4.4e-3)
FSDP_LOSS_RTOL = 2e-2

DECODE = dict(decode_capacity=8, decode_max_len=2048,
              decode_prompt_buckets=(16, 48, 256))
PROMPT_LENS = (9, 40, 33, 200)   # buckets 16, 48 (not a power of two), 256
MAX_NEW = 16

RESNET_SIZE = 224
RESNET_BATCH = 128
RESNET_STEPS = 4
RESNET_CLASSES = 8      # labels used (the head keeps its 1000 outputs)
SERVE_MAX_BATCH = 4     # ladder 1, 2, 4
SERVE_BATCHES = (2, 3)  # land in buckets 2 and 4
# served (bucketed executables) vs unserved predict: two programs over
# the same f32 weights at the chip's default matmul precision
SERVE_RTOL, SERVE_ATOL = 2e-2, 1e-3


class SmokeError(RuntimeError):
    """A phase's check failed."""


def require(cond, what):
    if not cond:
        raise SmokeError(what)


def say(**fields):
    print(json.dumps(fields, default=str), flush=True)


class Phase:
    """Wall seconds, compiles and cache hits of one phase, read from the
    repo's own XLA hooks (observability.profile)."""

    def __init__(self, name, prof):
        self.name, self.prof = name, prof

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.s0 = self.prof.snapshot()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        import jax
        s1 = self.prof.snapshot()
        hit = "/jax/compilation_cache/cache_retrieval_time_sec"
        mem = jax.local_devices()[0].memory_stats() or {}
        say(phase=self.name, ok=True,
            seconds=round(time.perf_counter() - self.t0, 2),
            compiles=s1["compiles"] - self.s0["compiles"],
            compile_seconds=round(
                s1["compile_seconds"] - self.s0["compile_seconds"], 2),
            cache_hits=(s1["events"].get(hit, 0)
                        - self.s0["events"].get(hit, 0)),
            peak_hbm_bytes=mem.get("peak_bytes_in_use"))
        return False


# ------------------------------------------------------------------ data
def lm_tokens(rows, seq_len, vocab):
    """Log-uniform (Zipf-like) token ranks: a few steps of training learn
    the marginal distribution, so the loss falls."""
    rng = np.random.default_rng(SEED)
    seq = np.floor(np.exp(rng.uniform(0.0, np.log(vocab),
                                      (rows, seq_len + 1)))) - 1
    seq = np.clip(seq, 0, vocab - 1).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def resnet_images(rows, size):
    """Images whose mean colour names their class, plus noise."""
    rng = np.random.default_rng(SEED + 1)
    y = rng.integers(0, RESNET_CLASSES, rows).astype(np.int32)
    palette = rng.uniform(-1.0, 1.0, (RESNET_CLASSES, 3)).astype(np.float32)
    x = rng.normal(0.0, 0.5, (rows, size, size, 3)).astype(np.float32)
    return x + palette[y][:, None, None, :], y


# ---------------------------------------------------------------- phases
def assert_kernel_ran(step_text, n_layers):
    """Every layer's attention must run the compiled pallas kernels —
    forward, dq and dkv — not the blockwise scan or the interpreter
    (both of which lower to plain HLO, with no custom call)."""
    calls = step_text.count("tpu_custom_call")
    require(calls >= 3 * n_layers,
            f"{calls} tpu_custom_call in the compiled LM step, want "
            f"fwd+dq+dkv for each of {n_layers} layers: attention did "
            "not take the pallas kernel")


def check_losses(name, losses, steps):
    require(len(losses) == steps, f"{name}: {len(losses)} losses for "
            f"{steps} steps")
    require(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    require(losses[-1] < losses[0]
            and np.mean(losses[steps // 2:]) < np.mean(losses[:steps // 2]),
            f"{name}: loss is not falling: {losses}")


def fit_lm(lm_cfg, batch, steps, mesh=None, strategy="replicate"):
    """compile + fit the LM for ``steps`` steps; returns the model, the
    per-step losses and the step profiler's timeline."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.models import TransformerLM
    lm = TransformerLM(**lm_cfg)
    lm.compile("adam", "class_nll", mesh=mesh, strategy=strategy,
               seed=SEED, compute_dtype=jnp.bfloat16)
    prof = lm.trainer.enable_step_profiler()
    x, y = lm_tokens(batch * steps, lm_cfg["seq_len"], lm_cfg["vocab_size"])
    hist = lm.fit(x, y, batch_size=batch, nb_epoch=1, shuffle=False)
    return lm, [float(v) for v in hist["loss"]], prof.timeline(), (x, y)


def phase_train_lm(lm_cfg, batch, steps):
    lm, losses, timeline, (x, y) = fit_lm(lm_cfg, batch, steps)
    check_losses("train-lm", losses, steps)
    # the step compiled once, in the first step, and never again
    per_step = [e.get("compiles", 0) for e in timeline]
    require(per_step[0] == 1 and sum(per_step) == 1,
            f"train-lm: step compiles per step {per_step}, want one "
            "compile in the first step and none after")
    compiled = lm.trainer.lower_train_step(x[:batch], y[:batch]).compile()
    text = compiled.as_text()
    assert_kernel_ran(text, lm_cfg["n_layers"])
    mem = compiled.memory_analysis()
    say(phase="train-lm", batch=batch, steps=steps, losses=losses,
        attention="pallas flash (tpu_custom_call x%d)"
        % text.count("tpu_custom_call"),
        step_compile_ms=timeline[0].get("compile_ms"),
        step_temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        step_argument_bytes=getattr(mem, "argument_size_in_bytes", None))
    return lm


def phase_serve_lm(lm, decode_kwargs, prompt_lens, max_new):
    from analytics_zoo_tpu.serving import ModelRegistry
    rng = np.random.default_rng(SEED + 2)
    vocab = lm.hyper["vocab_size"]
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in prompt_lens]
    with ModelRegistry() as reg:
        reg.deploy("lm", net=lm, **decode_kwargs)
        got = reg.generate("lm", prompts, max_new)
        decode = reg.metrics("lm")["lm"]["serving"]["decode"]
    require(len(got) == len(prompts), "serve-lm: missing continuations")
    for p, g in zip(prompts, got):
        want = lm.generate(p[None, :], max_new)[0, len(p):]
        require(np.array_equal(np.asarray(g), want),
                f"serve-lm: prompt len {len(p)}: engine {list(g)} != "
                f"TransformerLM.generate {list(want)}")
    say(phase="serve-lm", prompts=list(prompt_lens), max_new=max_new,
        buckets=list(decode_kwargs["decode_prompt_buckets"]),
        greedy_token_exact=True, tokens=int(sum(len(g) for g in got)),
        decode={k: decode[k] for k in (
            "admitted", "tokens", "steps", "fused_dispatches",
            "prefill_hits", "prefill_misses", "prefill_compile_time_s")})


def fit_resnet(size, batch, steps):
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.image.classification import resnet50
    net = resnet50(input_shape=(size, size, 3))
    net.compile("adam", "sparse_categorical_crossentropy", seed=SEED,
                compute_dtype=jnp.bfloat16)
    x, y = resnet_images(batch * steps, size)
    hist = net.fit(x, y, batch_size=batch, nb_epoch=1, shuffle=False)
    return net, [float(v) for v in hist["loss"]]


def phase_train_resnet(size, batch, steps):
    net, losses = fit_resnet(size, batch, steps)
    check_losses("train-resnet50", losses, steps)
    say(phase="train-resnet50", batch=batch, steps=steps, image=size,
        losses=losses)
    return net


def phase_serve_resnet(net, size, max_batch, batches):
    x, _ = resnet_images(sum(batches), size)
    im = net.to_serving(coalescing=True, max_batch_size=max_batch,
                        warmup_shapes=(size, size, 3))
    try:
        warmed = dict(im.serving_stats()["misses"])
        worst, off = 0.0, 0
        for n in batches:
            xs = x[off:off + n]
            off += n
            got = np.asarray(im.predict(xs))
            want = np.asarray(net.predict(xs, batch_size=max_batch))
            require(got.shape == want.shape == (n, 1000)
                    and np.isfinite(got).all(),
                    f"serve-resnet50: bad output {got.shape}")
            worst = max(worst, float(np.max(np.abs(got - want))))
            require(np.allclose(got, want, rtol=SERVE_RTOL,
                                atol=SERVE_ATOL),
                    f"serve-resnet50: batch {n} differs from predict by "
                    f"{worst}")
        stats = im.serving_stats()
    finally:
        im.close()
    require(stats["misses"] == warmed
            and all(v == 1 for v in warmed.values())
            and set(warmed) == set(stats["buckets"]),
            f"serve-resnet50: want one miss per bucket, got "
            f"{stats['misses']} over buckets {stats['buckets']}")
    say(phase="serve-resnet50", batches=list(batches),
        buckets=list(stats["buckets"]), misses=stats["misses"],
        hits=stats["hits"], max_abs_diff_vs_predict=worst,
        tolerance={"rtol": SERVE_RTOL, "atol": SERVE_ATOL})


# ------------------------------------------------------------ four chips
def shard_report(tree):
    """Per-device bytes of a sharded tree, and the devices that hold it."""
    import jax
    per_dev, total = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                     + sh.data.nbytes)
    return per_dev, total


def phase_fsdp_vs_single(lm_cfg, batch, steps, max_share=0.55):
    import jax
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    devs = jax.devices()
    one = mesh_lib.create_mesh({"data": 1}, devices=devs[:1])
    _, base, _, _ = fit_lm(lm_cfg, batch, steps, mesh=one)
    check_losses("single-device", base, steps)
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 2}, devices=devs[:4])
    say(mesh={"data": 2, "fsdp": 2},
        device_grid=[[(d.id, getattr(d, "coords", None)) for d in row]
                     for row in mesh.devices])
    lm, losses, _, _ = fit_lm(lm_cfg, batch, steps, mesh=mesh,
                              strategy="fsdp")
    check_losses("fsdp", losses, steps)
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, base)]
    require(max(rel) <= FSDP_LOSS_RTOL,
            f"fsdp: per-step loss differs from one device by {rel} "
            f"(tolerance {FSDP_LOSS_RTOL})")
    st = lm.trainer.state
    report = {}
    for name, tree in (("params", st.params), ("opt_state", st.opt_state)):
        per_dev, total = shard_report(tree)
        require(len(per_dev) == 4,
                f"fsdp: {name} shards live on devices {sorted(per_dev)}")
        share = max(per_dev.values()) / total
        # fsdp=2 halves what is big enough to shard; the small leaves
        # (biases, norms) stay whole
        require(share <= max_share,
                f"fsdp: {name} per-device share {share:.3f} of "
                f"replicated, want about 1/2 (<= {max_share})")
        report[name] = {"per_device_bytes": per_dev, "total_bytes": total,
                        "max_share": round(share, 4)}
    say(phase="fsdp-vs-single", batch=batch, steps=steps,
        single_losses=base, fsdp_losses=losses,
        max_rel_diff=max(rel), tolerance=FSDP_LOSS_RTOL, shards=report)


def phase_replicas(size, prof):
    import jax
    from analytics_zoo_tpu.models.image.classification import resnet50
    net = resnet50(input_shape=(size, size, 3))
    x, _ = resnet_images(1, size)
    net.ensure_inference_ready()   # weight init compiles land here
    c0 = prof.snapshot()["compiles"]
    im = net.to_serving(replicas="all", max_batch_size=1,
                        warmup_shapes=(size, size, 3))
    try:
        n = im.n_replicas
        require(n == len(jax.local_devices()) == 4,
                f"replicas: {n} replicas on "
                f"{len(jax.local_devices())} devices")
        warm = dict(im.serving_stats()["misses"])
        outs = [np.asarray(im.predict(x)) for _ in range(2 * n)]
        stats = im.serving_stats()
    finally:
        im.close()
    require(warm == {1: 1} and stats["misses"] == warm,
            f"replicas: want one compile for the one bucket, "
            f"got misses {stats['misses']}")
    deploy_compiles = prof.snapshot()["compiles"] - c0
    require(deploy_compiles == 1,
            f"replicas: {deploy_compiles} XLA compiles to place one "
            "bucket on four devices, want one compile and three loads")
    spread = stats["replica_dispatches"]
    require(len(spread) == 4 and all(v > 0 for v in spread.values()),
            f"replicas: dispatches not spread over four: {spread}")
    require(all(np.array_equal(o, outs[0]) for o in outs[1:]),
            "replicas: answers differ across replicas")
    say(phase="replicas", replicas=n, bucket_compiles=warm, loads=n - 1,
        xla_compiles_during_deploy=deploy_compiles,
        replica_dispatches=spread, bit_identical=True)


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != args.chips:
        print(f"chip_smoke: need {args.chips} TPU device(s), jax found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        return 2

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu import native
    from analytics_zoo_tpu.common.context import enable_compile_cache
    from analytics_zoo_tpu.observability import profile
    t0 = time.perf_counter()
    prof = profile.install()
    say(jax=jax.__version__, backend=jax.default_backend(),
        device_kind=devs[0].device_kind, devices=len(devs),
        compile_cache_dir=enable_compile_cache(),
        native_available=native.available(),
        native_build_error=native.build_error())
    zoo.init_nncontext(app_name="chip_smoke")

    if args.chips == 4:
        with Phase("fsdp-vs-single", prof):
            phase_fsdp_vs_single(LM, LM_BATCH, LM_STEPS)
        with Phase("replicas", prof):
            phase_replicas(RESNET_SIZE, prof)
    else:
        with Phase("train-lm", prof):
            lm = phase_train_lm(LM, LM_BATCH, LM_STEPS)
        with Phase("serve-lm", prof):
            phase_serve_lm(lm, DECODE, PROMPT_LENS, MAX_NEW)
        del lm
        with Phase("train-resnet50", prof):
            net = phase_train_resnet(RESNET_SIZE, RESNET_BATCH,
                                     RESNET_STEPS)
        with Phase("serve-resnet50", prof):
            phase_serve_resnet(net, RESNET_SIZE, SERVE_MAX_BATCH,
                               SERVE_BATCHES)
    total = prof.snapshot()
    say(total_seconds=round(time.perf_counter() - t0, 2),
        compiles=total["compiles"],
        compile_seconds=total["compile_seconds"])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
