"""Device: the share of device 0's synchronous-line busy time in operations
that no part of `perfbench.scopes`' table claims. Also logs the whole
(phase, part) table, the largest unattributed operations and the largest
straddling fusions as ``[scopes]`` lines: the operator's view of where the
step's time goes, and of how far a single part can be trusted."""

from perfbench import harness, scopes


def read(run):
    device = run["trace"].devices[0]
    names = scopes.instruction_scopes(run["built"]["compiled_text"])
    table = scopes.step_table(device, names)
    busy = sum(table.values())
    if not busy:
        return None
    scopes.log_table(table, harness.log)
    for phase, category, path, ms in scopes.top_unattributed(device, names):
        harness.log(f"[scopes] unattributed {ms:9.3f} ms  {phase}  "
                    f"{category}  {path or '(no op_name)'}")
    for part, other, ms in scopes.straddles(device,
                                            run["built"]["compiled_text"]):
        harness.log(f"[scopes] straddling   {ms:9.3f} ms  booked to {part}, "
                    f"in fusions that also hold {other}")
    return 100.0 * (scopes.total(table, parts=(scopes.UNATTRIBUTED,))
                    or 0.0) / busy
