from deeplearning4j_tpu.profiler.profiler import (
    OpProfiler,
    PanicException,
    ProfilerConfig,
    ProfilingListener,
    mfu,
)

__all__ = [
    "OpProfiler",
    "PanicException",
    "ProfilerConfig",
    "ProfilingListener",
    "mfu",
]
