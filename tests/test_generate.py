"""TransformerLM.generate — KV-cache decode correctness (VERDICT r4 #3).

The gold standard is the TRAINING forward (the graph model's full
causal pass, already oracle-tested): the cached decode path must
reproduce its per-position log-probabilities exactly, and greedy
generation must equal repeated full-forward argmax."""

import numpy as np
import pytest
import jax

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.models import TransformerLM


VOCAB, SEQ = 59, 32


def _trained_lm(**kw):
    zoo.init_nncontext()
    m = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                      d_model=32, n_heads=2, **kw)
    m.compile({"name": "adam", "lr": 5e-3}, "class_nll")
    rng = np.random.default_rng(0)
    # learnable structure: next token = (token + 1) % VOCAB
    x = rng.integers(0, VOCAB, (128, SEQ))
    y = (x + 1) % VOCAB
    m.fit(x, y, batch_size=32, nb_epoch=8)
    return m


def _full_forward_argmax(m, ids):
    """argmax of the graph model's log-probs at the LAST position of a
    padded-to-seq_len window (teacher forcing oracle)."""
    pad = np.zeros((ids.shape[0], SEQ - ids.shape[1]), ids.dtype)
    window = np.concatenate([ids, pad], axis=1)
    logp = m.predict(window, batch_size=ids.shape[0])
    return np.argmax(logp[:, ids.shape[1] - 1], axis=-1)


def test_greedy_matches_repeated_full_forward():
    """Each greedily generated token must equal the full (uncached)
    forward's argmax at that position — pins prefill AND every cached
    step to the training path."""
    m = _trained_lm()
    prompt = np.random.default_rng(1).integers(0, VOCAB, (3, 8))
    out = m.generate(prompt, max_new_tokens=6, temperature=0.0)
    assert out.shape == (3, 14)
    np.testing.assert_array_equal(out[:, :8], prompt)
    for t in range(6):
        expect = _full_forward_argmax(m, out[:, :8 + t])
        np.testing.assert_array_equal(
            out[:, 8 + t], expect,
            err_msg=f"cached decode diverged at step {t}")


def test_generate_trained_structure():
    """The trained (x+1)%V structure must come out of the decoder."""
    m = _trained_lm()
    prompt = np.arange(10, 18)[None, :]
    out = m.generate(prompt, max_new_tokens=5, temperature=0.0)
    np.testing.assert_array_equal(out[0, 8:], (np.arange(18, 23)) % VOCAB)


def test_sampling_modes():
    m = _trained_lm()
    prompt = np.random.default_rng(2).integers(0, VOCAB, (2, 8))
    g1 = m.generate(prompt, max_new_tokens=4, temperature=1.0, seed=0)
    g2 = m.generate(prompt, max_new_tokens=4, temperature=1.0, seed=1)
    assert g1.shape == g2.shape == (2, 12)
    # astronomically unlikely to collide on every token if sampling works
    assert not np.array_equal(g1, g2)
    # same seed -> deterministic
    g3 = m.generate(prompt, max_new_tokens=4, temperature=1.0, seed=0)
    np.testing.assert_array_equal(g1, g3)
    # top-k=1 at any temperature collapses to greedy
    gk = m.generate(prompt, max_new_tokens=4, temperature=0.7, top_k=1,
                    seed=5)
    gg = m.generate(prompt, max_new_tokens=4, temperature=0.0)
    np.testing.assert_array_equal(gk, gg)


def test_top_p_modes():
    """Nucleus sampling through the compiled-scan path: deterministic
    at a fixed seed, and a vanishing nucleus collapses to greedy (the
    top token always survives the truncation)."""
    m = _trained_lm()
    prompt = np.random.default_rng(4).integers(0, VOCAB, (2, 8))
    g1 = m.generate(prompt, max_new_tokens=4, temperature=0.9,
                    top_p=0.9, seed=3)
    g2 = m.generate(prompt, max_new_tokens=4, temperature=0.9,
                    top_p=0.9, seed=3)
    np.testing.assert_array_equal(g1, g2)
    tiny = m.generate(prompt, max_new_tokens=4, temperature=0.9,
                      top_p=1e-9, seed=3)
    gg = m.generate(prompt, max_new_tokens=4, temperature=0.0)
    np.testing.assert_array_equal(tiny, gg)
    # composes with top_k, and beam search still rejects sampling knobs
    gc = m.generate(prompt, max_new_tokens=4, temperature=0.8, top_k=9,
                    top_p=0.8, seed=7)
    assert gc.shape == (2, 12)
    with pytest.raises(ValueError, match="deterministic"):
        m.generate(prompt, max_new_tokens=2, num_beams=2, top_p=0.9)


def test_sample_temperature_zero_is_argmax_property():
    """The pinned property: ``_sample(temperature=0)`` IS argmax —
    on the static (python-scalar) path the scan decoder compiles, AND
    on the traced per-slot path the decode engine's step plan selects
    through — over randomized logits scales/shapes, so scan-decode and
    step-decode share one greedy-consistent sampling implementation."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.models.generation import _sample

    dyn = jax.jit(lambda lg, key, t, k, p: _sample(lg, key, t, k, p))
    stat_sampled = jax.jit(
        lambda lg, key: _sample(lg, key, 0.8, 7, 0.9))
    rng = np.random.default_rng(11)
    for trial in range(25):
        scale = float(rng.uniform(0.1, 20.0))
        logits = jnp.asarray(
            rng.normal(size=(5, 33)).astype(np.float32) * scale)
        key = jax.random.PRNGKey(trial)
        greedy = np.argmax(np.asarray(logits), axis=-1)
        # static greedy: the pre-sampling plan, literally an argmax
        np.testing.assert_array_equal(
            np.asarray(_sample(logits, key, 0.0, None, None)), greedy)
        # traced temperature == 0 with sampling knobs riding along
        # (top_k = 0 / top_p = 1 are the engine's disabled encodings)
        np.testing.assert_array_equal(
            np.asarray(dyn(logits, key, jnp.float32(0.0),
                           jnp.int32(0), jnp.float32(1.0))), greedy)
        # traced-vs-static equivalence of the ENABLED path: the
        # engine's dynamic top-k/top-p masks truncate identically to
        # the scan path's baked-in constants, so one request samples
        # the same token through either decoder
        np.testing.assert_array_equal(
            np.asarray(dyn(logits, key, jnp.float32(0.8),
                           jnp.int32(7), jnp.float32(0.9))),
            np.asarray(stat_sampled(logits, key)))


def test_generate_moe_variant():
    """The Switch-MoE sublayer decodes through the same cache path.
    capacity_factor = n_experts makes BOTH paths drop-free (decode is
    always drop-free; the full-forward oracle needs the headroom) so
    they agree exactly."""
    m = _trained_lm(moe_every=2, n_experts=4, capacity_factor=4.0)
    prompt = np.random.default_rng(3).integers(0, VOCAB, (2, 8))
    out = m.generate(prompt, max_new_tokens=4, temperature=0.0)
    for t in range(4):
        expect = _full_forward_argmax(m, out[:, :8 + t])
        np.testing.assert_array_equal(out[:, 8 + t], expect,
                                      err_msg=f"moe decode step {t}")


def test_generate_from_ring_trained_model():
    """A model TRAINED with sequence-parallel ring attention decodes
    through the same single-chip KV-cache path (the decode reads params
    by name and computes its own attention, so the training
    implementation must not matter): greedy output equals an
    implementation='auto' model carrying the same weights."""
    import jax as _jax
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    zoo.init_nncontext()
    n = len(_jax.devices())
    mesh = create_mesh({"data": 1, "seq": n})
    ring = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                         d_model=32, n_heads=2, implementation="ring")
    ring.compile({"name": "adam", "lr": 5e-3}, "class_nll", mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.integers(0, VOCAB, (64, SEQ))
    ring.fit(x, (x + 1) % VOCAB, batch_size=16, nb_epoch=2)

    prompt = np.random.default_rng(5).integers(0, VOCAB, (2, 8))
    out_ring = ring.generate(prompt, max_new_tokens=5, temperature=0.0)

    auto = TransformerLM(vocab_size=VOCAB, seq_len=SEQ, n_layers=2,
                         d_model=32, n_heads=2)
    auto.compile({"name": "adam", "lr": 5e-3}, "class_nll")
    auto.transfer_weights_from(ring)
    out_auto = auto.generate(prompt, max_new_tokens=5, temperature=0.0)
    np.testing.assert_array_equal(out_ring, out_auto)


def test_generate_validation():
    m = _trained_lm()
    with pytest.raises(ValueError, match="max_len"):
        m.generate(np.zeros((1, 30), np.int32), max_new_tokens=10)
    with pytest.raises(ValueError, match="prompt_ids"):
        m.generate(np.zeros((8,), np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="deterministic"):
        m.generate(np.zeros((1, 4), np.int32), max_new_tokens=2,
                   num_beams=3, temperature=0.5)
    with pytest.raises(ValueError, match="vocab_size"):
        m.generate(np.zeros((1, 4), np.int32), max_new_tokens=2,
                   num_beams=VOCAB + 1)
    with pytest.raises(ValueError, match="max_new_tokens >= 1"):
        m.generate(np.zeros((1, 4), np.int32), max_new_tokens=0,
                   num_beams=2)
    # max_new_tokens=0 returns the prompt unchanged on both sampling
    # paths (no plan built)
    p0 = np.asarray([[3, 1, 4, 1], [5, 9, 2, 6]], np.int32)
    np.testing.assert_array_equal(m.generate(p0, max_new_tokens=0), p0)
    np.testing.assert_array_equal(
        m.generate(p0, max_new_tokens=0,
                   prompt_lengths=np.array([4, 2])), p0)
    # the compiled plan object keeps .lower(): a plan can be lowered unrun
    from analytics_zoo_tpu.models.generation import build_generate_fn
    assert hasattr(build_generate_fn(m.hyper, 4, 2, 0.0, None), "lower")


def test_ragged_prompts_match_per_row_generation():
    """prompt_lengths: each right-padded row must decode EXACTLY as it
    would alone, unpadded — per-row positions, per-row cache slots, and
    the per-row last-real-token prefill handoff all pinned by the
    strongest oracle there is (the same model, one row at a time)."""
    m = _trained_lm()
    rng = np.random.default_rng(7)
    lengths = np.array([8, 5, 3])
    s_p, max_new = 8, 5
    prompt = np.zeros((3, s_p), np.int64)
    rows = []
    for i, L in enumerate(lengths):
        rows.append(rng.integers(0, VOCAB, L))
        prompt[i, :L] = rows[i]
    out = m.generate(prompt, max_new_tokens=max_new, temperature=0.0,
                     prompt_lengths=lengths)
    assert out.shape == (3, s_p + max_new)
    for i, L in enumerate(lengths):
        solo = m.generate(rows[i][None, :], max_new_tokens=max_new,
                          temperature=0.0)
        np.testing.assert_array_equal(out[i, :L], rows[i])
        np.testing.assert_array_equal(
            out[i, L:L + max_new], solo[0, L:],
            err_msg=f"row {i} (length {L}) diverged from its solo run")
        assert (out[i, L + max_new:] == 0).all()
    # full-length prompt_lengths degenerate to the uniform path
    uniform = m.generate(prompt, max_new_tokens=max_new,
                         temperature=0.0)
    ragged_full = m.generate(prompt, max_new_tokens=max_new,
                             temperature=0.0,
                             prompt_lengths=np.full(3, s_p))
    np.testing.assert_array_equal(ragged_full, uniform)


def test_ragged_prompt_validation():
    m = _trained_lm()
    p = np.zeros((2, 6), np.int32)
    with pytest.raises(ValueError, match="prompt_lengths must be"):
        m.generate(p, max_new_tokens=2, prompt_lengths=np.array([6]))
    with pytest.raises(ValueError, match=r"\[1, 6\]"):
        m.generate(p, max_new_tokens=2,
                   prompt_lengths=np.array([6, 7]))
    with pytest.raises(ValueError, match="not supported with beam"):
        m.generate(p, max_new_tokens=2, num_beams=2,
                   prompt_lengths=np.array([6, 5]))


def test_beam_width_one_equals_greedy():
    """W=1 beam search degenerates to greedy decoding exactly (same
    prefill, same cached steps, argmax == top-1)."""
    m = _trained_lm()
    prompt = np.random.default_rng(4).integers(0, VOCAB, (3, 8))
    greedy = m.generate(prompt, max_new_tokens=5, temperature=0.0)
    # num_beams=1 routes to the sampling path; drive the beam machinery
    # itself at W=1 through the module function
    from analytics_zoo_tpu.models.generation import (_backtrack_beams,
                                                     build_beam_fn)
    import jax.numpy as jnp
    trainer = m.ensure_inference_ready()
    fn = build_beam_fn(m.hyper, 8, 5, 1)
    seqs, _ = _backtrack_beams(*fn(trainer.state.params,
                                   jnp.asarray(prompt)))
    np.testing.assert_array_equal(seqs[:, 0], greedy[:, 8:])


def test_beam_search_finds_higher_likelihood_than_greedy():
    """The canonical beam property: the returned sequence's TRUE
    teacher-forced log-prob (scored by the full training forward) is >=
    the greedy sequence's, and the internal cumulative score must equal
    that independent score — pinning the beam bookkeeping (cache
    gathers, parent tracking) to the training path."""
    m = _trained_lm()
    prompt = np.random.default_rng(6).integers(0, VOCAB, (4, 8))
    max_new = 5

    def scored(ids):
        """Sum of per-step log-probs of ids[:, 8:] under the full
        forward (teacher forcing)."""
        pad = np.zeros((ids.shape[0], SEQ - ids.shape[1]), ids.dtype)
        logp = m.predict(np.concatenate([ids, pad], 1),
                         batch_size=ids.shape[0])
        tot = np.zeros(ids.shape[0])
        for t in range(max_new):
            pos = 8 + t - 1  # logits at pos predict token at pos+1
            tot += logp[np.arange(ids.shape[0]), pos, ids[:, 8 + t]]
        return tot

    greedy = m.generate(prompt, max_new_tokens=max_new, temperature=0.0)
    beam = m.generate(prompt, max_new_tokens=max_new, num_beams=4)
    assert beam.shape == greedy.shape
    np.testing.assert_array_equal(beam[:, :8], prompt)
    s_greedy, s_beam = scored(greedy), scored(beam)
    assert (s_beam >= s_greedy - 1e-4).all(), (s_beam, s_greedy)

    from analytics_zoo_tpu.models.generation import (_backtrack_beams,
                                                     build_beam_fn)
    import jax.numpy as jnp
    trainer = m.ensure_inference_ready()
    fn = build_beam_fn(m.hyper, 8, max_new, 4)
    seqs, scores = _backtrack_beams(*fn(trainer.state.params,
                                        jnp.asarray(prompt)))
    np.testing.assert_array_equal(seqs[:, 0], beam[:, 8:])
    full = np.concatenate([prompt.astype(np.int32), seqs[:, 0]], 1)
    np.testing.assert_allclose(scores[:, 0], scored(full), rtol=1e-4,
                               atol=1e-4)
    # beams arrive best-first
    assert (np.diff(scores, axis=1) <= 1e-6).all(), scores
