"""The reader ``decode_pick_argmax_share`` on ``spans_fixture.json``'s
rows, whose one dispatch carries no ``pick_sorted`` (a program from
before the stat), and on the same rows with dispatches that do."""

import json
import os

import pytest

from benchmark import program_spans, run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6        # ns
DISPATCHER = 3


def read_with(*stats):
    """What the reader gives on the fixture's rows and one more dispatch
    span for each of ``stats``, inside the traced window."""
    with open(os.path.join(HERE, "spans_fixture.json")) as f:
        rows = [tuple(r) for r in json.load(f)]
    for i, extra in enumerate(stats):
        lo = (84 + 2 * i) * MS
        rows.append(("/host:CPU", DISPATCHER, "zoo/decode/dispatch", lo,
                     lo + MS, extra))
    ctx = {**harness.resolve("gpt2m-chat-closed"),
           "program_spans": program_spans.build(rows)}
    return harness.load_module("layer_metrics",
                               "decode_pick_argmax_share").read(ctx)


def test_nothing_to_read_without_the_stat():
    assert read_with() is None
    assert read_with({"k": 2, "live": 1}) is None


@pytest.mark.parametrize("stats,share", [
    ([{"k": 4, "pick_sorted": 0}, {"k": 2, "pick_sorted": 0}], 100.0),
    ([{"k": 4, "pick_sorted": 1}], 0.0),
    # weighted by the steps of a dispatch, not one a dispatch
    ([{"k": 4, "pick_sorted": 0}, {"k": 1, "pick_sorted": 1},
      {"k": 1, "pick_sorted": 1}, {"k": 2, "pick_sorted": 0}], 75.0),
])
def test_share_of_the_steps_that_picked_by_argmax(stats, share):
    assert read_with(*stats) == pytest.approx(share)
