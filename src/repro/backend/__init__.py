"""Backend portability layer: capability probe + calibrated dispatch.

Public API:
    BackendSpec, UnsupportedOnBackend        — capability description
    TPU_PEAKS / UnknownDevice                — per-kind TPU roofline peaks
    TPU_PALLAS / GPU_PALLAS / CPU_XLA /
    CPU_INTERPRET / XLA_REF                  — built-in specs
    current_backend / probe_backend /
    resolve_backend / use_backend /
    register_backend                         — registry (REPRO_BACKEND env)
    DispatchTable / default_table /
    calibrate_dispatch                       — shape -> kernel-path table
    calibrate_overlap / calibrated_network   — measured ring-pipeline
                                               overlap efficiency -> NetworkModel
"""

from .spec import (BackendSpec, UnsupportedOnBackend,  # noqa: F401
                   BUILTIN_SPECS, CPU_INTERPRET, CPU_XLA, GPU_PALLAS,
                   TPU_PALLAS, TPU_PEAKS, UnknownDevice, XLA_REF)
from .registry import (BACKEND_ENV, current_backend, known_backends,  # noqa: F401
                       probe_backend, register_backend, resolve_backend,
                       use_backend)
from .dispatch import (DispatchTable, calibrate_dispatch,  # noqa: F401
                       calibrate_overlap, calibrate_short_wide_ratio,
                       calibrated_network, default_table,
                       overlap_efficiency_from_times)
