"""Operations and bytes that the ``granitemoehybrid`` family's decode
step and prefill REQUIRE, from the configuration's widths and the
traffic: what the algorithm needs, never what a compiler emitted
(``costs.py``'s rule; this family's functions live here, beside its
adapter and reference).  Weights, key/value slabs and convolution
windows count 2 bytes an element (bfloat16 as served), the SSM state 4
(float32)."""

ITEM = 2            # bfloat16: weights, slabs, convolution windows
STATE_ITEM = 4      # float32: the SSM state, and the kernel's x, dt, B, C, y
PEAK_CHUNK = 256    # the scan's chunk (``mamba_chunk_size``)


def dims(cfg):
    heads, hd, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_d_state"])
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"d": cfg["hidden_size"], "f": cfg["shared_intermediate_size"],
            "vocab": cfg["vocab_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
            "m_heads": heads, "m_hd": hd, "inner": heads * hd, "n": n,
            "conv_dim": heads * hd + 2 * n, "k": cfg["mamba_d_conv"],
            "chunk": cfg.get("mamba_chunk_size", PEAK_CHUNK),
            "mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def mixer_matmul_params(cfg):
    """A Mamba mixer's two projections: in_proj d x (2 d_inner + 2N +
    heads) and out_proj d_inner x d."""
    c = dims(cfg)
    return c["d"] * (c["inner"] + c["conv_dim"] + c["m_heads"]) \
        + c["inner"] * c["d"]


def mixer_params(cfg):
    """Every leaf of a mixer: the projections, the convolution's kernel
    and bias, A_log, D, dt_bias and the gated norm's gain."""
    c = dims(cfg)
    return mixer_matmul_params(cfg) + (c["k"] + 1) * c["conv_dim"] \
        + 3 * c["m_heads"] + c["inner"]


def mlp_params(cfg):
    """input_linear d x 2f and output_linear f x d."""
    c = dims(cfg)
    return 3 * c["d"] * c["f"]


def attention_params(cfg):
    """Wq, Wo (d x h hd each) and Wk, Wv (d x kv hd each)."""
    c = dims(cfg)
    return 2 * c["d"] * c["hd"] * (c["h"] + c["kv"])


def n_params(cfg):
    """The whole model: every layer's mixer or attention, MLP and two
    norm gains, the table (tied head) and the final norm's gain."""
    c = dims(cfg)
    per = mlp_params(cfg) + 2 * c["d"]
    return (c["mamba"] * (mixer_params(cfg) + per)
            + c["attention"] * (attention_params(cfg) + per)
            + c["vocab"] * c["d"] + c["d"])


def matmul_params_per_token(cfg):
    """Weights every token of a step or a prompt multiplies by: the
    mixers' and attention layers' projections and the MLPs (the head is
    counted apart: a prompt takes it for its last position alone)."""
    c = dims(cfg)
    return (c["mamba"] * mixer_matmul_params(cfg)
            + c["attention"] * attention_params(cfg)
            + (c["mamba"] + c["attention"]) * mlp_params(cfg))


def state_elems(cfg):
    """One slot's SSM state in one layer: heads x head_dim x d_state."""
    c = dims(cfg)
    return c["m_heads"] * c["m_hd"] * c["n"]


def state_update_flops(cfg):
    """One slot's state update in one layer: ``S' = a S + u B^T`` (two
    multiplies and an add an element) and ``y = S' C`` (a multiply and an
    add an element)."""
    return 5 * state_elems(cfg)


def decode_flops_per_token(cfg, positions):
    """One output token of one slot whose context holds ``positions``:
    2 x the projections and MLPs, the convolution (2 K a channel), the
    state update, 4 h hd per live cached row of each attention layer, and
    2 d vocab for the tied head."""
    c = dims(cfg)
    return (2 * matmul_params_per_token(cfg)
            + c["mamba"] * (2 * c["k"] * c["conv_dim"]
                            + state_update_flops(cfg))
            + c["attention"] * 4 * c["h"] * c["hd"] * positions
            + 2 * c["d"] * c["vocab"])


def decode_bytes_per_step(cfg, slots, positions):
    """HBM bytes ONE decode step needs: every weight once at 2 bytes (the
    table once, for the head), each live slot's SSM state read and
    written at 4 bytes and its convolution window read and written at 2,
    and the live rows of keys and values of the attention layers at 2."""
    c = dims(cfg)
    weights = ITEM * n_params(cfg)
    state = 2 * STATE_ITEM * slots * c["mamba"] * state_elems(cfg)
    window = 2 * ITEM * slots * c["mamba"] * (c["k"] - 1) * c["conv_dim"]
    cache = ITEM * slots * c["attention"] * positions * 2 * c["kv"] * c["hd"]
    return weights + state + window + cache


def ssm_decode_bytes_per_call(cfg, slots):
    """One call of the state-update kernel (one layer, all slots), for
    ``slots`` live slots: each one's state read and written at 4 bytes,
    its ``dt x`` (heads x head_dim) and ``exp(dt A)`` (heads), ``B`` and
    ``C`` (d_state each) in and ``y`` (heads x head_dim) out, float32."""
    c = dims(cfg)
    io = 2 * c["m_heads"] * c["m_hd"] + c["m_heads"] + 2 * c["n"]
    return STATE_ITEM * slots * (2 * state_elems(cfg) + io)


def causal_pairs(positions, chunk):
    """(query, key) pairs inside the chunks of a prompt of
    ``positions``: each chunk's lower triangle, the diagonal included."""
    full, rest = divmod(positions, chunk)
    return full * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def scan_flops(cfg, positions):
    """The chunked scan of one layer over a prompt of ``positions``: in
    each chunk ``C B^T`` (2 N a pair) and its masked product with ``dt
    x`` (2 heads head_dim a pair), each chunk's state (2 heads head_dim N
    a position) and what the state before its chunk gives each position
    (the same again)."""
    c = dims(cfg)
    pairs = causal_pairs(positions, c["chunk"])
    return (2 * pairs * (c["n"] + c["inner"])
            + 4 * positions * c["inner"] * c["n"])


def scan_bytes(cfg, positions):
    """What the scan of one layer takes in and gives out over a prompt
    of ``positions``: ``x``, ``B``, ``C`` (2 bytes) and ``dt`` (4) in,
    ``y`` (4) out, and the final state (4)."""
    c = dims(cfg)
    return (positions * (ITEM * (c["inner"] + 2 * c["n"])
                         + STATE_ITEM * (c["m_heads"] + c["inner"]))
            + STATE_ITEM * state_elems(cfg))


def scan_floor_s(cfg, positions, peaks):
    """The least time one layer's scan can take."""
    return max(scan_flops(cfg, positions) / peaks["bf16_flops_per_s"],
               scan_bytes(cfg, positions) / peaks["hbm_bytes_per_s"])


def prefill_flops(cfg, positions):
    """One admission of a prompt of ``positions`` tokens (its own length,
    not its bucket's): per token 2 x the projections and MLPs and the
    convolution; each Mamba layer's scan; 4 h hd per (query, visible
    key) pair of each attention layer; the head for the LAST position
    alone."""
    c = dims(cfg)
    pairs = positions * (positions + 1) // 2
    return (positions * (2 * matmul_params_per_token(cfg)
                         + c["mamba"] * 2 * c["k"] * c["conv_dim"])
            + c["mamba"] * scan_flops(cfg, positions)
            + c["attention"] * 4 * c["h"] * c["hd"] * pairs
            + 2 * c["d"] * c["vocab"])
