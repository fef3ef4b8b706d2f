"""`moe_held_row_share_pct` from a routing counter written by hand, and on a
family without the counter."""

import types

import numpy as np

from perfbench import cell


def _run(counts, tokens=(2, 4096), k=4):
    return {"routing_counts": counts,
            "built": {"batch": {"input_ids": np.zeros(tokens, np.int32)}},
            "cell": types.SimpleNamespace(
                config={"model": {"num_experts_per_tok": k}})}


def _read(run):
    return cell.layer_reader("moe_held_row_share_pct")(run)


def test_the_share_of_the_sorted_rows_held_experts_work_on():
    # 8192 tokens x 4 slots = 32,768 rows a layer; two layers hold 4,096
    # and 8,192 assignments: 12.5% and 25%, 18.75% together
    counts = np.array([[512] * 8, [1024] * 8])
    assert _read(_run(counts)) == 18.75
    # every assignment held: all rows live
    assert _read(_run(np.array([[4096] * 8]))) == 100.0
    # none held (a step whose tokens all chose absent experts) reads 0
    assert _read(_run(np.zeros((5, 8), np.int64))) == 0.0


def test_a_family_without_the_counter_reports_nothing():
    assert _read(_run(None)) is None
