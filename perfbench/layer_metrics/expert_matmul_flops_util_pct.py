"""Models and kernels: the grouped expert matmuls' share of their compute
roofline, whatever implements them: 6 FLOPs per parameter of one expert
(3 x hidden x expert width) for every assignment the router actually made to
a held expert this step (`moe_scopes.routing_counts`), over the device time
under ``moe/experts`` (forward + backward; the activation between the two
matmuls is in it) times the chip's bf16 peak. Forward work recomputed in the
backward pass adds to the time and not to the FLOPs, so it cannot read
high."""

from perfbench import moe_scopes


def read(run):
    ms = moe_scopes.ms_under(run, moe_scopes.EXPERTS)
    counts = moe_scopes.routing_counts(run)
    if ms is None or counts is None:
        return None
    flops = run["cell"].family.expert_matmul_flops(
        run["cell"].config["model"], float(counts.sum()))
    return 100.0 * flops / (ms * 1e-3 * run["peaks"]["bf16_flops_per_s"])
