"""What the readers of the routed (``cohere2_moe``) cells share: sums of
a stat over the traced spans of one name, and the device's operations
cut down to the step programs.  ``program_spans.py`` stays as it is and
lends ``of_run``, ``Spans`` and ``in_scope``."""

import re

from benchmark import program_spans

#: scope paths of operations inside the decode step programs
#: (``jit(step)/...``, ``jit(stepk)/...``: ``profile.PROGRAM_STEP*``)
STEP_PATH = re.compile(r"^jit\(step")


def stat_sum(spans, span, stat):
    """Sum of ``stat`` over the traced ``zoo/<span>`` spans, and how
    many of them carry it."""
    total = count = 0
    for e in (spans.named(span) if spans else ()):
        if stat in e[4]:
            total += e[4][stat]
            count += 1
    return total, count


def step_programs_only(spans):
    """The same traced run with the device's operations of the step
    programs alone (an admit plan runs the same scopes over a prompt)."""
    return program_spans.Spans(
        (spans.lo, spans.hi), spans.host,
        [e for e in spans.ops if e[4] and STEP_PATH.match(e[4])],
        spans.modules)


def experts_hit_per_layer_step(spans, cfg):
    """Held experts with at least one token, a layer and a step, as the
    program counted them on its ``zoo/decode/fanout`` spans; ``None``
    where no span carries the count."""
    hit, n = stat_sum(spans, "decode/fanout", "moe_experts_hit")
    steps, _ = stat_sum(spans, "decode/fanout", "steps")
    if not n or not steps:
        return None
    return hit / (steps * cfg["num_hidden_layers"])
