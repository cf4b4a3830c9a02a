"""The one place where the ``granitemoehybrid`` family's configuration
keys meet the program's model class.  The plain reference beside it is
``benchmark/reference/granitehybrid.py``; what the family's work costs
is ``benchmark/costs_granitehybrid.py``."""


def build(cfg, traffic):
    """The program's model for this configuration, not yet compiled."""
    from analytics_zoo_tpu.models import GraniteHybridLM
    n = cfg["num_hidden_layers"]
    return GraniteHybridLM(
        vocab_size=cfg["vocab_size"],
        seq_len=traffic.get("seq_len", cfg["n_positions"]),
        max_len=cfg["n_positions"], n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["shared_intermediate_size"],
        layer_types=cfg["layer_types"][:n],
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_head_dim=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk=cfg["mamba_chunk_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"])
