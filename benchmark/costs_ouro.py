"""Operations and bytes that the ``ouro`` family's decode step and
prefill REQUIRE, from the configuration's widths and the traffic: what
the algorithm needs, never what a compiler emitted (``costs.py``'s rule;
this family's functions live here, beside its adapter and reference).
Weights and key/value slabs count 2 bytes an element (bfloat16 as
served).  Every token runs the layers ``total_ut_steps`` times: a pass
reads every layer's weights again and its own keys and values."""

ITEM = 2            # bfloat16: weights, slabs


def dims(cfg):
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "layers": cfg["num_hidden_layers"],
            "passes": cfg["total_ut_steps"]}


def attention_params(cfg):
    """Wq, Wo (d x h hd each) and Wk, Wv (d x kv hd each)."""
    c = dims(cfg)
    return 2 * c["d"] * c["hd"] * (c["h"] + c["kv"])


def mlp_params(cfg):
    """input_linear d x 2f (gate and up) and output_linear f x d."""
    c = dims(cfg)
    return 3 * c["d"] * c["f"]


def layer_params(cfg):
    """One layer: attention, MLP and four norm gains."""
    return attention_params(cfg) + mlp_params(cfg) + 4 * dims(cfg)["d"]


def n_params(cfg):
    """The whole model: the layers once (shared by the passes), the table
    and the untied head, the final norm's gain and the exit gate."""
    c = dims(cfg)
    return (c["layers"] * layer_params(cfg) + 2 * c["vocab"] * c["d"]
            + c["d"] + c["d"] + 1)


def matmul_params_per_pass(cfg):
    """Weights a token multiplies by in one pass: every layer's
    projections and MLP (the head is counted apart)."""
    c = dims(cfg)
    return c["layers"] * (attention_params(cfg) + mlp_params(cfg))


def kv_row_bytes(cfg):
    """One position's keys and values in one layer and one pass."""
    c = dims(cfg)
    return ITEM * 2 * c["kv"] * c["hd"]


def decode_flops_per_token(cfg, positions):
    """One output token of one slot whose context holds ``positions``:
    per pass 2 x the projections and MLPs and 4 h hd per live cached row
    of each layer; 2 d vocab for the head once."""
    c = dims(cfg)
    per_pass = (2 * matmul_params_per_pass(cfg)
                + c["layers"] * 4 * c["h"] * c["hd"] * positions)
    return c["passes"] * per_pass + 2 * c["d"] * c["vocab"]


def decode_bytes_per_step(cfg, slots, positions):
    """HBM bytes ONE decode step needs: every layer's weights once a pass
    (a pass cannot start before the last one's last layer), the head
    once (the table's rows of the step's tokens are few), each live
    slot's live keys and values of every layer and every pass
    (``positions`` a slot), and the new rows written."""
    c = dims(cfg)
    weights = ITEM * c["passes"] * c["layers"] * layer_params(cfg)
    head = ITEM * c["d"] * c["vocab"]
    cache = (kv_row_bytes(cfg) * slots * c["passes"] * c["layers"]
             * (positions + 1))
    return weights + head + cache


def decode_attn_bytes_per_call(cfg, rows):
    """One call of the decode kernel (one layer, one pass, all slots)
    for ``rows`` live rows in all: each row's keys and values read
    once."""
    return kv_row_bytes(cfg) * rows


def prefill_flops(cfg, positions):
    """One admission of a prompt of ``positions`` tokens (its own length,
    not its bucket's), every pass: per token 2 x the projections and
    MLPs, 4 h hd per (query, visible key) pair of each layer; the head
    for the LAST position alone."""
    c = dims(cfg)
    pairs = positions * (positions + 1) // 2
    per_pass = (positions * 2 * matmul_params_per_pass(cfg)
                + c["layers"] * 4 * c["h"] * c["hd"] * pairs)
    return c["passes"] * per_pass + 2 * c["d"] * c["vocab"]
