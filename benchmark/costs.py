"""Operations and bytes that the work REQUIRES, from the configuration's
widths alone: what the algorithm needs, never what a compiler emitted
and never recomputation.  One function per step or kernel, the formula
in its docstring.  The table of peaks is ``peaks.json``; a device kind
that is not in it is an error, never a default."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The row of ``peaks.json`` for exactly this ``device_kind``."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(has {sorted(table)}): add a row with its source")
    return table[device_kind]


def lm_matmul_params(cfg):
    """Parameters that take part in a matmul for every token:
    n_layer * (4 d^2 + 2 d d_ff) + d * vocab (the head; the embedding
    lookup is a gather and the biases and norms are not matmuls)."""
    d, ff = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * ff) + d * cfg["vocab_size"]


def causal_attention_flops(seq, d_model, queries=None):
    """QK^T and PV of ONE layer's causal attention, forward, all heads:
    query t sees t+1 keys, each key costs 2*d_model for the score and
    2*d_model for the value, so sum_t 4 d (t+1) = 2 d s (s+1): the
    causal half is counted once.  ``queries``: only the last that many
    positions are queries (decode: 1)."""
    if queries is None:
        return 2 * d_model * seq * (seq + 1)
    first = seq - queries
    return 4 * d_model * sum(range(first + 1, seq + 1))


def lm_train_flops_per_sample(cfg, seq):
    """Forward + backward of one packed sequence of ``seq`` tokens:
    3 * (2 * matmul_params * seq + n_layer * causal_attention_flops):
    the backward costs twice the forward; recomputation not counted."""
    fwd = (2 * lm_matmul_params(cfg) * seq
           + cfg["n_layer"] * causal_attention_flops(seq, cfg["n_embd"]))
    return 3 * fwd


def lm_decode_flops_per_token(cfg, live_positions):
    """One output token of one slot whose cache holds ``live_positions``
    (its own included): 2 * matmul_params + n_layer * 4 d live."""
    return (2 * lm_matmul_params(cfg)
            + cfg["n_layer"] * 4 * cfg["n_embd"] * live_positions)


def lm_decode_bytes_per_step(cfg, live_positions_total, weight_bytes=4,
                             cache_bytes=4):
    """HBM bytes ONE decode step needs: every matmul weight once
    (shared by all slots) + the keys and values of the live positions of
    all slots: n_layer * 2 * d * live_total * cache_bytes.  Counts the
    work, not the implementation (a step that reads the whole max_len
    slab reads more than this)."""
    return (lm_matmul_params(cfg) * weight_bytes
            + cfg["n_layer"] * 2 * cfg["n_embd"] * live_positions_total
            * cache_bytes)


def flash_bytes(batch, seq, d_model, itemsize=2):
    """HBM bytes one layer's flash attention needs, forward + backward:
    forward reads q, k, v and writes o (4 tensors of b*s*d); backward
    reads q, k, v, o, do and writes dq, dk, dv (8): 12 b s d itemsize.
    The s*s scores never leave the chip: that is the kernel's point."""
    return 12 * batch * seq * d_model * itemsize


def flash_flops(batch, seq, d_model):
    """One layer's flash attention, forward + backward, causal half
    counted once: forward 2 matmuls, backward 4 (dv, dp, dq, dk; the
    recomputed scores are recomputation and not counted): 3x forward."""
    return 3 * batch * causal_attention_flops(seq, d_model)


# He et al. 2015, Table 1, 50-layer column: (blocks, bottleneck width)
_RESNET50_STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def resnet50_forward_macs(image=224, classes=1000):
    """Multiply-accumulates of one image's forward pass, convolutions
    and the classifier only: conv = k*k*cin*cout*hout*wout.  Stem 7x7/2
    to 64, 3x3/2 max-pool, then bottlenecks (1x1 w, 3x3 w, 1x1 4w) with
    the stride on the FIRST 1x1 and on the 1x1 projection shortcut of
    each stage's first block, as the paper has it and as this repo's
    ``resnet50`` builds it (models/image/classification.py).  3.86e9 at
    224, the paper's "3.8 x 10^9 FLOPs" (multiply-adds)."""
    h = image // 2
    macs = 7 * 7 * 3 * 64 * h * h
    h //= 2
    cin = 64
    for stage, (blocks, w) in enumerate(_RESNET50_STAGES):
        for b in range(blocks):
            hout = h // (2 if (b == 0 and stage > 0) else 1)
            macs += cin * w * hout * hout            # 1x1 reduce (strided)
            macs += 9 * w * w * hout * hout          # 3x3
            macs += w * 4 * w * hout * hout          # 1x1 expand
            if b == 0:
                macs += cin * 4 * w * hout * hout    # projection
            cin, h = 4 * w, hout
    return macs + cin * classes


def resnet50_train_flops_per_sample(image=224, classes=1000):
    """3 * 2 * forward MACs: backward twice the forward."""
    return 6 * resnet50_forward_macs(image, classes)
