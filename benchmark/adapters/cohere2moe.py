"""The one place where the ``cohere2_moe`` family's configuration keys
meet the program's model class.  The plain reference beside it is
``benchmark/reference/cohere2moe.py``; what the family's work costs is
``benchmark/costs_cohere2moe.py``."""


def build(cfg, traffic):
    """The program's model for this configuration, not yet compiled."""
    from analytics_zoo_tpu.models import CommandAPlusLM
    n = cfg["num_hidden_layers"]
    return CommandAPlusLM(
        vocab_size=cfg["vocab_size"],
        seq_len=traffic.get("seq_len", cfg["n_positions"]),
        max_len=cfg["n_positions"], n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        n_experts=cfg["num_experts_published"],
        top_k=cfg["num_experts_per_tok"],
        n_shared=cfg["num_shared_experts"],
        experts_held=tuple(cfg["experts_held"]),
        sliding_window=cfg["sliding_window"],
        layer_types=cfg["layer_types"][:n], rope_theta=cfg["rope_theta"],
        layer_norm_eps=cfg["layer_norm_eps"],
        logit_scale=cfg["logit_scale"])
