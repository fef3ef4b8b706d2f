"""Schedule builder: collective instructions in the compiled step's text
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute;
an async pair counts once). An exact count; a program with none reports
nothing."""

from perfbench.harness import count_collectives


def read(run):
    total = sum(count_collectives(run["built"]["compiled_text"]).values())
    return float(total) if total else None
