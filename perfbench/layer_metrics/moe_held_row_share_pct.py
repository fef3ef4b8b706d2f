"""Models and kernels: the share of the expert layers' sorted rows that some
held expert works on, from the program's counter
(`moe_scopes.routing_counts`: assignments per held expert per expert layer on
the run's batch and weights): ``100 * sum(assignments) / (tokens * experts
per token * expert layers)``. The buffers are ``tokens * experts per token``
rows whatever the routing (the layer is dropless); this share of them is
what the row kernels of `ops.moe_rows` move, and what their saving is
proportional to. A property of the traffic and the weights, not of the
program: about 100 / (expert-parallel group size)."""

from perfbench import moe_scopes


def share_pct(counts, tokens: int, experts_per_token: int) -> float:
    """``counts`` ``[expert layers, experts held]``."""
    return 100.0 * float(counts.sum()) / (
        tokens * experts_per_token * counts.shape[0])


def read(run):
    counts = moe_scopes.routing_counts(run)
    if counts is None or not counts.size:
        return None
    tokens = run["built"]["batch"]["input_ids"].size
    return share_pct(counts, tokens,
                     run["cell"].config["model"]["num_experts_per_tok"])
