"""Models and kernels: the KDA scan's share of its roofline: the least time
the chip could take for the scans of a step, the larger of the family's
`kda_scan_flops` over the bf16 peak and `kda_scan_bytes` over the HBM
bandwidth (`perfbench/peaks.json`), over the device time under ``kda/scan``
(`kda_scan_ms`: forward + backward, whatever implements the scan). The work
is the family's count (the chunked algorithm's matmuls at chunk 64 once
forward and twice back; the inputs read and the outputs written once each
way), a function of the model and the token count only, so a later kernel is
read against the same yardstick; recomputation and every intermediate that
goes through HBM add to the time and not to the work, so it cannot read
high. A family without the functions, or a program without the scope, reads
nothing."""

from perfbench.layer_metrics import kda_scan_ms


def floor_s(family, model: dict, tokens: int, peaks: dict) -> float:
    """The scans' least time a step, seconds."""
    return max(family.kda_scan_flops(model, tokens)
               / peaks["bf16_flops_per_s"],
               family.kda_scan_bytes(model, tokens)
               / peaks["hbm_bytes_per_s"])


def read(run):
    cell = run["cell"]
    fam = cell.family
    if not (hasattr(fam, "kda_scan_flops") and hasattr(fam, "kda_scan_bytes")):
        return None
    ms = kda_scan_ms.read(run)
    if ms is None:
        return None
    tokens = fam.tokens_per_step(cell.traffic["batch_per_chip"],
                                 cell.traffic["seq_len"])
    return 100.0 * floor_s(fam, cell.config["model"], tokens,
                           run["peaks"]) / (ms * 1e-3)
