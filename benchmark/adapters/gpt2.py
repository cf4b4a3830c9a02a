"""The one place where the ``gpt2`` family's configuration keys meet
the program's model class.  The plain reference beside it is
``benchmark/reference/gpt2.py``."""


def build(cfg, traffic):
    """The program's model for this configuration, not yet compiled."""
    from analytics_zoo_tpu.models import TransformerLM
    return TransformerLM(
        vocab_size=cfg["vocab_size"],
        seq_len=traffic.get("seq_len", cfg["n_positions"]),
        max_len=cfg["n_positions"], n_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], n_heads=cfg["n_head"],
        d_ff=cfg.get("n_inner") or 4 * cfg["n_embd"],
        dropout=cfg["resid_pdrop"])


class Feed:
    """Packed token rows from the seed, held in memory: the program's
    ``Dataset`` and the reference's arrays are slices of one array."""

    def __init__(self, traffic, cfg, seed):
        from benchmark import traffic as gen
        self.batch = traffic["batch"]
        self.x, self.y = gen.packed_tokens(traffic, cfg["vocab_size"], seed)

    def dataset(self, first_step, steps=None):
        """What ``fit`` is fed for steps [first_step, first_step+steps)."""
        from analytics_zoo_tpu.data.dataset import Dataset
        lo = first_step * self.batch
        hi = None if steps is None else lo + steps * self.batch
        return Dataset.from_ndarray(self.x[lo:hi], self.y[lo:hi])

    def reference(self, steps):
        """The first ``steps`` batches as (steps, batch, seq) arrays."""
        n = steps * self.batch
        return (self.x[:n].reshape(steps, self.batch, -1),
                self.y[:n].reshape(steps, self.batch, -1))


def train_flops_per_sample(cfg, traffic):
    from benchmark import costs
    return costs.lm_train_flops_per_sample(cfg, traffic["seq_len"])
