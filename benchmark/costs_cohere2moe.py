"""Operations and bytes that the ``cohere2_moe`` family's decode step
and prefill REQUIRE, from the configuration's widths and what the traffic routed:
what the algorithm needs, never what a compiler emitted (``costs.py``'s
rule; this family's functions live here, beside its adapter and
reference).  Bytes count the weights and the cache at the 2 bytes the
configuration stores them in."""

ITEM = 2        # bfloat16, weights and cache


def dims(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"d": d, "f": f, "h": h, "kv": kv, "hd": hd,
            "layers": len(kinds),
            "sliding": sum(k == "sliding_attention" for k in kinds),
            "full": sum(k == "full_attention" for k in kinds),
            "window": cfg["sliding_window"],
            "held": cfg["experts_held"][1],
            "published": cfg["num_experts_published"],
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["num_shared_experts"], "vocab": cfg["vocab_size"]}


def attention_params(cfg):
    """One layer's Wq, Wk, Wv, Wo: d (h + 2 kv) hd + h hd d."""
    c = dims(cfg)
    return c["d"] * (c["h"] + 2 * c["kv"]) * c["hd"] + c["h"] * c["hd"] * c["d"]


def expert_params(cfg):
    """One gated expert: Wgate, Wup (d f each) and Wdown (f d)."""
    c = dims(cfg)
    return 3 * c["d"] * c["f"]


def live_rows(cfg, positions):
    """Rows of key/value cache one slot's decode step needs, summed over
    the layers, for a slot whose cache holds ``positions`` (its own
    included): every one in a full layer, the last ``window`` in a
    sliding one."""
    c = dims(cfg)
    return (c["full"] * positions
            + c["sliding"] * min(positions, c["window"]))


def held_pairs_per_token(cfg):
    """Routed (token, expert) pairs a layer that fall on held experts,
    for uniform routing: top_k * held / published."""
    c = dims(cfg)
    return c["top_k"] * c["held"] / c["published"]


def decode_flops_per_token(cfg, positions):
    """One output token of one slot: per layer 2 x (attention
    projections + router + the shared experts + the held experts its
    token was routed to, in expectation) + 4 h hd per live cached row;
    + 2 d vocab for the head."""
    c = dims(cfg)
    per_layer = 2 * (attention_params(cfg) + c["d"] * c["published"]
                     + (c["shared"] + held_pairs_per_token(cfg))
                     * expert_params(cfg))
    return (c["layers"] * per_layer
            + 4 * c["h"] * c["hd"] * live_rows(cfg, positions)
            + 2 * c["d"] * c["vocab"])


def visible_keys(positions, window=None):
    """Keys that the queries of a causal prompt of ``positions`` see,
    summed over the queries: query i sees i + 1 keys, at most
    ``window`` of them in a sliding layer."""
    if window is None or positions <= window:
        return positions * (positions + 1) // 2
    return window * (window + 1) // 2 + (positions - window) * window


def prefill_flops(cfg, positions):
    """One admission of a prompt of ``positions`` tokens (its own
    length, not the bucket it is padded to): per token and layer what a
    decode step's token takes but the attention, which is 4 h hd per
    (query, visible key) pair, and the head for the LAST position
    alone."""
    c = dims(cfg)
    per_token = 2 * (attention_params(cfg) + c["d"] * c["published"]
                     + (c["shared"] + held_pairs_per_token(cfg))
                     * expert_params(cfg))
    pairs = (c["full"] * visible_keys(positions)
             + c["sliding"] * visible_keys(positions, c["window"]))
    return (c["layers"] * per_token * positions
            + 4 * c["h"] * c["hd"] * pairs + 2 * c["d"] * c["vocab"])


def experts_hit_per_layer(cfg, slots):
    """Held experts with at least one token in a step of ``slots`` live
    slots, in expectation under uniform routing: held * (1 - (1 -
    top_k / published) ** slots).  A reader that has the program's own
    count uses that instead."""
    c = dims(cfg)
    return c["held"] * (1.0 - (1.0 - c["top_k"] / c["published"]) ** slots)


def decode_bytes_per_step(cfg, slots, positions, experts_hit=None):
    """HBM bytes ONE decode step needs: the non-expert weights once
    (attention, router, the head's table), the shared experts, the held
    experts that at least one token HIT (``experts_hit`` a layer; the
    expectation where not given), and the keys and values inside the
    window of every live slot: 2 x kv hd a row."""
    c = dims(cfg)
    if experts_hit is None:
        experts_hit = experts_hit_per_layer(cfg, slots)
    weights = (c["layers"] * (attention_params(cfg)
                              + c["d"] * c["published"]
                              + (c["shared"] + experts_hit)
                              * expert_params(cfg))
               + c["d"] * c["vocab"])
    cache = 2 * c["kv"] * c["hd"] * slots * live_rows(cfg, positions)
    return ITEM * (weights + cache)


def experts_step_floor_s(cfg, slots, experts_hit, peaks):
    """The least time one LAYER's held experts can take in a step: its
    routed pairs' flops over the peak, or the bytes of the experts hit
    over the HBM peak, whichever is larger."""
    flops = 2 * slots * held_pairs_per_token(cfg) * expert_params(cfg)
    nbytes = ITEM * experts_hit * expert_params(cfg)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def decode_attn_bytes_per_call(cfg, rows_live):
    """One call of the decode attention kernel (one layer, all slots):
    the live rows of keys and of values, 2 x rows x kv hd x 2 bytes."""
    c = dims(cfg)
    return 2 * rows_live * c["kv"] * c["hd"] * ITEM
