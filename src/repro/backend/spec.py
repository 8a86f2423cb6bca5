"""Hardware capability descriptions: the queryable side of portability.

The paper's portability story is hipify + a rocBLAS host dispatcher whose
transition points were set per-GPU by benchmarking — the *application*
never learns which kernel ran.  Ginkgo's HIP port and the tile-centric
mixed-precision GEMM line of work make the same argument: what a backend
can do (datatypes, tile alignments, peak rates) belongs in one hardware
description that kernel selection *queries*, not in per-call-site flags.

:class:`BackendSpec` is that description for this repo: a frozen,
hashable record of one execution backend — platform, Pallas
availability, whether f64 survives inside Pallas kernels, tile/padding
alignments, roofline peaks, and default block sizes.  Specs are *static
capability tables*; the probing that picks one for the current process
lives in :mod:`repro.backend.registry`, and the shape-dependent kernel
choice on top of a spec lives in :mod:`repro.backend.dispatch`.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


class UnsupportedOnBackend(TypeError):
    """An *explicitly requested* kernel path cannot run on this backend.

    Raised only for explicit requests (``force="pallas"`` dispatch);
    automatic dispatch never raises — it falls back to a supported path
    instead.
    """


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Capabilities of one execution backend.

    ``platform``/``device_kind`` identify the hardware ("" = filled in
    from the probed device, see ``registry.resolve_backend``).  ``pallas``
    says the Pallas kernels can run at all; ``pallas_interpret`` that they
    run in interpret mode (CPU validation); ``pallas_f64`` that f64 data
    survives *inside* Pallas kernels (false on TPU — no f64 datapath).
    ``reference`` forces the pure-jnp oracle lowerings (``kernels.ref``),
    bypassing both Pallas and the traffic-fused XLA formulations — the
    numerical ground truth every other backend is compared against.

    ``f64`` says the backend runs f64 phases at all: the "d" rung of the
    precision ladder.  A TPU has no f64 FFT and no f64 Pallas datapath,
    so a plan with a "d" stage raises :class:`UnsupportedOnBackend` there
    (``core.pipeline.run_stages``) instead of failing in the compiler or,
    with x64 off, silently computing in f32.

    ``sublane``/``lane`` are the padding alignments the kernel wrappers
    must honor; ``peak_flops``/``hbm_bandwidth``/``link_bandwidth`` feed
    the roofline model (``launch.roofline``); ``default_block_n``/
    ``default_block_s`` seed the dispatch table's tile sizes.

    ``tile_precision`` gates the tile-centric mixed-precision GEMM paths
    (DESIGN.md §8): whether this backend's Phase-3 lowerings honor a
    per-tile precision map.  Requesting ``tile_map=`` on a backend
    without it raises :class:`UnsupportedOnBackend` (explicit request,
    never a silent downgrade).

    ``overlap_chunks`` is the backend's pipelined-collective depth
    (DESIGN.md §9): how many chunks ``overlap="auto"`` splits the Phase-3
    contraction into when the dispatch table decides pipelining pays.
    Set from how many collectives the platform can realistically keep in
    flight, not from the mesh.
    """

    name: str
    platform: str = ""
    device_kind: str = ""
    pallas: bool = False
    pallas_interpret: bool = False
    pallas_f64: bool = False
    f64: bool = True
    reference: bool = False
    tile_precision: bool = False
    sublane: int = 8
    lane: int = 128
    default_block_n: int = 512
    default_block_s: int = 128
    overlap_chunks: int = 4
    peak_flops: float = 0.0          # FLOP/s, native matmul precision
    hbm_bandwidth: float = 0.0       # B/s per device
    link_bandwidth: float = 0.0      # B/s per interconnect link

    def fingerprint(self) -> str:
        """Stable identity for cache keys: backend + hardware it bound to."""
        return f"{self.name}@{self.platform}:{self.device_kind}"

    def ladder(self) -> tuple[str, ...]:
        """The precision levels this backend runs, highest first: d/s/h,
        or s/h without an f64 datapath.  The plan check, the set-up
        precision, autotune's default ladder and the lint sweep read it."""
        return ("d", "s", "h") if self.f64 else ("s", "h")

    @property
    def setup_dtype(self):
        """Compute dtype of the Phase-0 set-up FFT: the top of the ladder
        (f64 canonicalizes to f32 where x64 is off)."""
        return jnp.float64 if self.f64 else jnp.float32

    @property
    def plane_tile(self):
        """``(rows, lanes)`` that set-up pads the F_hat planes' (N_d, N_m)
        to whole multiples of, or None to store them unpadded.  A compiled
        Pallas Phase 3 reads its plane operands row-major in (sublane,
        lane) tiles; tile-padded planes are stored in that layout by the
        device's default, and every program reads the unpadded planes as a
        view of them, with no copy (DESIGN.md §12)."""
        if self.pallas and not self.pallas_interpret:
            return (self.sublane, self.lane)
        return None

    def roofline_peaks(self) -> tuple[float, float, float]:
        """``(peak_flops, hbm_bandwidth, link_bandwidth)``, or
        :class:`UnknownDevice` for a spec bound to a device whose peaks
        are not known: a roofline share against zero or borrowed peaks
        would be wrong."""
        peaks = (self.peak_flops, self.hbm_bandwidth, self.link_bandwidth)
        if not all(peaks):
            raise UnknownDevice(
                f"no roofline peaks for {self.fingerprint()!r}; add the "
                f"published peaks of device kind {self.device_kind!r} to "
                f"repro.backend.spec.TPU_PEAKS (known: {sorted(TPU_PEAKS)})")
        return peaks

    def pallas_supports(self, *dtypes) -> bool:
        """Whether the Pallas kernels can consume these dtypes here."""
        if not self.pallas:
            return False
        if any(jnp.dtype(dt) == jnp.float64 for dt in dtypes):
            return self.pallas_f64
        return True


class UnknownDevice(ValueError):
    """A roofline was asked of a spec bound to a TPU whose ``device_kind``
    has no entry in :data:`TPU_PEAKS`.  The operator still runs there;
    only its roofline shares are refused."""


# Published per-chip peaks of each TPU generation, keyed by the
# ``device_kind`` JAX reports.  TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links (50 GB/s per link).
TPU_PEAKS = {
    "TPU v5 lite": dict(peak_flops=197e12, hbm_bandwidth=819e9,
                        link_bandwidth=50e9),
}

# what a TPU of a kind missing from TPU_PEAKS binds: no peaks, so
# BackendSpec.roofline_peaks refuses it
NO_PEAKS = dict(peak_flops=0.0, hbm_bandwidth=0.0, link_bandwidth=0.0)


# ---------------------------------------------------------------------------
# Built-in specs.  TPU_PALLAS carries the v5e peaks as the modeled target
# (what compile-only roofline estimates price against); a spec bound to a
# real TPU takes its peaks from TPU_PEAKS by device kind
# (``registry._bind_device``).  GPU numbers are MI300X-class, the paper's
# newest target.  CPU peaks are order-of-magnitude placeholders — CPU
# runs are validation, never the roofline.
# ---------------------------------------------------------------------------

TPU_PALLAS = BackendSpec(
    name="tpu-pallas", platform="tpu", pallas=True, pallas_f64=False,
    f64=False, tile_precision=True, **TPU_PEAKS["TPU v5 lite"])

# pallas=False: the SBGEMV/SBGEMM kernels lower through the TPU Mosaic
# pipeline (pltpu CompilerParams) and do not
# run on the Triton backend yet — GPU auto-dispatch takes the traffic-
# fused XLA path; flip this when a GPU build of the kernels lands.
# tile_precision=False for the same reason: the tiled kernels are Mosaic
# lowerings, and the XLA fallback's pre-quantize pass has not been
# validated on the Triton pipeline — flip both together.
GPU_PALLAS = BackendSpec(
    name="gpu-pallas", platform="gpu", pallas=False, pallas_f64=False,
    peak_flops=1307e12, hbm_bandwidth=5300e9, link_bandwidth=64e9)

CPU_XLA = BackendSpec(
    name="cpu-xla", platform="cpu", pallas=False, tile_precision=True,
    peak_flops=1e12, hbm_bandwidth=100e9, link_bandwidth=25e9)

# CPU validation backend: the Pallas kernels via the interpreter.  Slow by
# construction — never auto-probed; select it explicitly (tests, examples).
CPU_INTERPRET = dataclasses.replace(
    CPU_XLA, name="cpu-interpret", pallas=True, pallas_interpret=True)

# Forced reference backend: oracle lowerings on whatever hardware is under
# us (platform filled at resolve time).  CI's numerical-parity leg.
XLA_REF = BackendSpec(
    name="xla-ref", platform="", reference=True, tile_precision=True,
    peak_flops=1e12, hbm_bandwidth=100e9, link_bandwidth=25e9)

BUILTIN_SPECS = {s.name: s for s in
                 (TPU_PALLAS, GPU_PALLAS, CPU_XLA, CPU_INTERPRET, XLA_REF)}
