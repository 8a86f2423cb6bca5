"""Backend selection: probe once per process, override by name.

``current_backend()`` answers "what hardware is under us" exactly once
per process (the answer cannot change mid-run: JAX pins its devices at
first use) and caches it.  The ``REPRO_BACKEND`` environment variable
overrides the probe by spec name — this is how CI runs the whole suite
against the forced ``xla-ref`` reference backend without touching any
call site — and :func:`use_backend` scopes an override to a ``with``
block for tests.

Custom specs register with :func:`register_backend`; resolution accepts
a spec instance, a registered name, or ``None`` (= probe).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Iterator, Optional, Union

import jax

from .spec import (BUILTIN_SPECS, CPU_XLA, GPU_PALLAS, NO_PEAKS, TPU_PALLAS,
                   TPU_PEAKS, BackendSpec)

BACKEND_ENV = "REPRO_BACKEND"

_REGISTRY: dict[str, BackendSpec] = dict(BUILTIN_SPECS)
_PROBED: Optional[BackendSpec] = None        # once-per-process probe cache
_OVERRIDE: Optional[BackendSpec] = None      # use_backend() scope

_PLATFORM_SPECS = {"tpu": TPU_PALLAS, "gpu": GPU_PALLAS, "cpu": CPU_XLA}


def register_backend(spec: BackendSpec) -> BackendSpec:
    """Add (or replace) a named spec in the registry."""
    _REGISTRY[spec.name] = spec
    return spec


def known_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _bind_device(spec: BackendSpec, device=None) -> BackendSpec:
    """Fill platform/device_kind from the actual device where unset.  A
    TPU spec bound to a real TPU takes that kind's roofline peaks from
    :data:`repro.backend.spec.TPU_PEAKS`; an unknown kind binds none, so
    it runs but its roofline is refused (``BackendSpec.roofline_peaks``)."""
    if spec.platform and spec.device_kind:
        return spec
    device = device or jax.devices()[0]
    kind = spec.device_kind or getattr(device, "device_kind", "")
    peaks = TPU_PEAKS.get(kind, NO_PEAKS) if (
        spec.platform == "tpu" and device.platform == "tpu") else {}
    return dataclasses.replace(
        spec, platform=spec.platform or device.platform, device_kind=kind,
        **peaks)


def probe_backend(device=None) -> BackendSpec:
    """Capability-probe the given (default: first) device.

    ``REPRO_BACKEND`` short-circuits the probe by registered spec name —
    the escape hatch for CI matrices and debugging.
    """
    env = os.environ.get(BACKEND_ENV)
    if env:
        return resolve_backend(env, device=device)
    device = device or jax.devices()[0]
    try:
        spec = _PLATFORM_SPECS[device.platform]
    except KeyError:
        raise ValueError(
            f"no backend spec for platform {device.platform!r}; register "
            f"one and select it with {BACKEND_ENV}") from None
    return _bind_device(spec, device)


def current_backend() -> BackendSpec:
    """The process-wide backend: probed once, then cached."""
    global _PROBED
    if _OVERRIDE is not None:
        return _OVERRIDE
    if _PROBED is None:
        _PROBED = probe_backend()
    return _PROBED


def resolve_backend(backend: Union[BackendSpec, str, None],
                    device=None) -> BackendSpec:
    """Spec instance / registered name / None (= current) -> bound spec."""
    if backend is None:
        return current_backend()
    if isinstance(backend, BackendSpec):
        return _bind_device(backend, device)
    try:
        spec = _REGISTRY[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; known: {known_backends()}"
        ) from None
    return _bind_device(spec, device)


@contextlib.contextmanager
def use_backend(backend: Union[BackendSpec, str]) -> Iterator[BackendSpec]:
    """Scope ``current_backend()`` to an override (tests, experiments)."""
    global _OVERRIDE
    prev, _OVERRIDE = _OVERRIDE, resolve_backend(backend)
    try:
        yield _OVERRIDE
    finally:
        _OVERRIDE = prev


def _reset_probe_cache() -> None:
    """Forget the cached probe (tests that monkeypatch REPRO_BACKEND)."""
    global _PROBED
    _PROBED = None
