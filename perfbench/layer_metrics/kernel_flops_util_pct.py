"""Models + kernels: the model's FLOPs per step and chip (shape-derived,
forward + backward, no recompute: the family's `flops_per_token`) over the
time the device was busy per step times the chip's bf16 peak: the compute
roofline share of the kernels while they run, idle time left out."""


def read(run):
    trace, built = run["trace"], run["built"]
    busy_s, _ = trace.busy_and_window_s()
    steps = len(trace.devices[0].modules)
    flops = built["flops_per_step"] / len(trace.devices)
    return 100.0 * flops / (busy_s / steps
                            * run["peaks"]["bf16_flops_per_s"])
