"""Native host runtime: C++ data pipeline + timers (csrc/dear_runtime.cpp),
beside a pure-numpy pipeline for hosts without a C++ toolchain."""

from dear_pytorch_tpu.runtime.pipeline import (  # noqa: F401
    NumpyPipeline,
    Pipeline,
    SyntheticSpec,
    bert_spec,
    image_spec,
    mnist_spec,
    native_available,
    now_ns,
)
