"""Backend bootstrap: host seconds around `lower().compile()` of the step
program: a compile on a checkout's first run, a cache read after it."""


def read(run):
    return run["built"]["spans"].get("compile_s")
