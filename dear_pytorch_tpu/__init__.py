"""dear_pytorch_tpu — a TPU-native decoupled-allreduce training framework.

A brand-new JAX/XLA framework with the capabilities of the reference
``lzhangbv/dear_pytorch`` (DeAR): data-parallel training in which the gradient
all-reduce is decoupled into a reduce-scatter (overlapped with the backward
pass) and an all-gather (overlapped with the next forward pass), with
threshold / nearby-layer tensor fusion, runtime fusion auto-tuning (Bayesian
optimization and wait-time heuristics), WFBP / MG-WFBP baseline schedules,
gradient compression, profiling, and a CNN + BERT benchmark harness.

Where the reference drives NCCL+MPI from a C++ extension and PyTorch autograd
hooks (reference: common/comm_core/src/communicator.cpp, dear/dear_dopt.py),
this framework expresses the same pipeline declaratively for TPUs: XLA
ReduceScatter/AllGather over ICI/DCN emitted at the right positions in a
single jitted train step, mesh/topology discovery from slice metadata instead
of MPI hostfiles, sharded (ZeRO-1) optimizer state, and overlap provided by
XLA's latency-hiding scheduler instead of CUDA side streams.

Public API (Horovod-style, mirroring reference dear/__init__.py:3-9):

    import dear_pytorch_tpu as dear
    dear.init()
    dear.rank(), dear.size(), dear.local_rank(), dear.barrier()
    step_fn, state = dear.build_train_step(...)   # the DeAR schedule
    dear.allreduce(x)                              # metric averaging
"""

from dear_pytorch_tpu.comm.backend import (  # noqa: F401
    init,
    is_initialized,
    shutdown,
    rank,
    size,
    local_rank,
    local_size,
    device_count,
    barrier,
    barriar,  # the reference's spelling (comm_core.cpp:15), drop-in parity
    global_mesh,
    set_global_mesh,
)
from dear_pytorch_tpu.config import DearConfig  # noqa: F401
from dear_pytorch_tpu.comm.communicator import Communicator  # noqa: F401
from dear_pytorch_tpu.comm import collectives  # noqa: F401
from dear_pytorch_tpu.comm.collectives import allreduce  # noqa: F401
from dear_pytorch_tpu import api  # noqa: F401
from dear_pytorch_tpu.api import (  # noqa: F401
    broadcast_optimizer_state,
    broadcast_parameters,
)
from dear_pytorch_tpu.parallel import (  # noqa: F401
    DearState,
    TrainStep,
    build_train_step,
)

__version__ = "0.1.0"
