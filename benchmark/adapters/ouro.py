"""The one place where the ``ouro`` family's configuration keys meet the
program's model class.  The plain reference beside it is
``benchmark/reference/ouro.py``; what the family's work costs is
``benchmark/costs_ouro.py``."""


def build(cfg, traffic):
    """The program's model for this configuration, not yet compiled."""
    from analytics_zoo_tpu.models import OuroLM
    return OuroLM(
        vocab_size=cfg["vocab_size"],
        seq_len=traffic.get("seq_len", cfg["n_positions"]),
        max_len=cfg["n_positions"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        early_exit_threshold=cfg["early_exit_threshold"])
