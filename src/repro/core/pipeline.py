"""Stage-graph pipeline core: typed stages -> compiled plans -> one executor.

The FFTMatvec pipeline (paper §2.4) is a linear graph of memory and compute
stages.  Rather than hand-writing one function per (direction x layout x
distribution) combination — which is how the forward/adjoint x single/multi-RHS
x local/sharded paths used to be eight near-identical copies — this module
*compiles* each variant to a :class:`Plan` (a tuple of :class:`Stage`
descriptors, each carrying its precision level and layout metadata) and runs
every plan through a single executor, :func:`run_plan`.

    stages      Pad, FFT, Reorder, Gemv (SBGEMV/SBGEMM by RHS count, or the
                per-bin Gram GEMM), IFFT, Mask, Unpad, Psum — each a frozen
                dataclass: hashable, so plans can be jit static arguments.
    plans       :func:`matvec_plan` (forward/adjoint, optionally ending in a
                mesh reduction) and :func:`gram_plan` (the fused Fourier-domain
                Gram operator, exact or circulant).
    executor    :func:`run_plan` folds the input through the stage list;
                multi-RHS blocks (R, N_t, S) are flattened to stacked planes
                at entry and restored at exit, so S = 1 and S > 1 share every
                stage implementation.
    distributed the mesh paths wrap the *same* plan (plus Psum stages) in
                ``shard_map`` — see :meth:`repro.core.FFTMatvec._apply`.

Precision semantics are unchanged from the hand-written pipelines: every
stage carries one level of the h < s < d ladder; reorder/mask memory stages
run at the lower of the adjacent compute phases' levels (paper footnote 8).

Instrumentation: :func:`stage_counts` counts a plan's stages statically and
:func:`record_stages` counts stages as the executor runs them (trace-time
under ``jit``) — this is how the fused Gram pipeline's "half the FFT/reorder
work" claim is verified in the tests rather than asserted.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Iterator, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.backend import (BackendSpec, DispatchTable, UnsupportedOnBackend,
                           default_table, resolve_backend)
from repro.kernels import ops as kops
from . import precision as prec
from .precision import PrecisionConfig

STAGE_KINDS = ("pad", "fft", "reorder", "gemv", "ifft", "mask", "unpad",
               "psum", "gemv_psum")

# How a psum stage lowers (paper §4.2.2 / DESIGN.md §6):
#   "psum"            one flat all-reduce over the whole axis group
#   "hierarchical"    staged per-axis reduction, fast (minor) tier first —
#                     the executed form of the paper's comm-aware blocking
#   "reduce_scatter"  reduce-scatter + all-gather decomposition of the
#                     flat all-reduce (bandwidth-optimal for large rows);
#                     falls back to flat psum when the carrier's leading
#                     dim does not tile over the group (the fallback is
#                     surfaced as ``collective:reduce_scatter:fallback``)
#   "ring"            explicit ppermute ring over the minor axis (g-1
#                     hops circulating the original partials) + a local
#                     reduction in canonical origin-rank order — the
#                     software-pipelined schedule (DESIGN.md §10): hop
#                     granularity the chunked gemv_psum super-stage can
#                     interleave with compute, with per-row accumulation
#                     order independent of chunking (bit-exact vs the
#                     serial plan).  Falls back to flat psum (surfaced as
#                     ``collective:ring:fallback``) when the plan carries
#                     no static group sizes — the ring permutation is a
#                     trace-time constant.
COLLECTIVE_KINDS = ("psum", "hierarchical", "reduce_scatter", "ring")


# ---------------------------------------------------------------------------
# Execution options: which backend lowers the plan, and per-stage overrides.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecOpts:
    """How a plan lowers: a backend + a dispatch table + stage overrides.

    Kernel selection is a property of the :mod:`repro.backend` layer,
    consulted once per stage at plan-lowering (trace) time — never
    per-call-site flags (the old ``use_pallas``/``interpret``/``block_*``
    kwarg tangle and its ``MatvecOptions`` shim are gone).

    ``backend``        a :class:`repro.backend.BackendSpec`, a registered
                       name ("tpu-pallas", "xla-ref", ...), or None — the
                       probed process backend (``REPRO_BACKEND`` env
                       override applies).
    ``dispatch``       transition-point table; None = the backend's
                       default (calibrate with
                       :func:`repro.backend.calibrate_dispatch`).
    ``block_n/_s``     SBGEMV/SBGEMM tile overrides (None = spec default).
    ``fuse_pad_cast``  pin the fused Pallas pad+cast kernels on/off; None
                       lets the dispatch table decide.  A True preference
                       the backend cannot honor (f64 stages) falls back —
                       memory ops are never worth an error.
    ``overlap``        chunk count of the pipelined ``gemv_psum``
                       super-stage (DESIGN.md §9): ``"auto"`` resolves it
                       per backend via
                       :meth:`repro.backend.DispatchTable.overlap_chunks`
                       (and may decline — K = 1 is the serial schedule),
                       an ``int`` pins K chunks, ``None`` never pipelines.
                       Single-device plans have no collective stage and
                       are unchanged by this knob.  Overlap changes the
                       *timing* of a plan, never its math: the chunked
                       schedule is row-partition-exact w.r.t. the serial
                       one.

    Hashable, so operators can pass it as a jit static argument.
    """

    backend: Union[BackendSpec, str, None] = None
    dispatch: Optional[DispatchTable] = None
    block_n: Optional[int] = None
    block_s: Optional[int] = None
    fuse_pad_cast: Optional[bool] = None
    overlap: Union[str, int, None] = "auto"

    def __post_init__(self):
        ov = self.overlap
        if not (ov is None or ov == "auto"
                or (isinstance(ov, int) and not isinstance(ov, bool)
                    and ov >= 1)):
            raise ValueError(f"overlap must be 'auto', a chunk count >= 1 "
                             f"or None, got {ov!r}")

    def resolve(self) -> "ResolvedOpts":
        """Bind to the concrete backend (the probe happens here, at
        lowering time; operator construction reads only the spec's
        ``setup_dtype``)."""
        spec = resolve_backend(self.backend)
        table = self.dispatch if self.dispatch is not None \
            else default_table(spec)
        return ResolvedOpts(spec=spec, table=table,
                            block_n=self.block_n or spec.default_block_n,
                            block_s=self.block_s or spec.default_block_s,
                            fuse_pad_cast=self.fuse_pad_cast,
                            overlap=self.overlap)


@dataclasses.dataclass(frozen=True)
class ResolvedOpts:
    """ExecOpts bound to a concrete spec — what the stage impls consume."""

    spec: BackendSpec
    table: DispatchTable
    block_n: int
    block_s: int
    fuse_pad_cast: Optional[bool]
    overlap: Union[str, int, None] = "auto"


def _resolved(opts) -> ResolvedOpts:
    return opts if isinstance(opts, ResolvedOpts) else opts.resolve()


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage: what to run, at which precision, on what layout.

    ``kind``       one of :data:`STAGE_KINDS`.
    ``level``      precision level ("h"/"s"/"d") the stage computes/stores
                   at.  For a psum stage this is the *communication*
                   precision: the reduction runs at it, but the carrier
                   dtype is restored afterwards (DESIGN.md §5) — a low
                   comm level is one rounding event per reduction, never a
                   downgrade of the downstream pipeline.
    ``adjoint``    gemv: conjugate-transpose flavor (F* pipelines).
    ``to_tosi``    reorder direction (SOTI -> TOSI or back).
    ``operand``    which operator planes feed a gemv stage ("F" for the
                   Fourier block column, "G" for precomputed Gram blocks).
    ``axis``       psum: mesh axis name — or a *tuple* of names, ordered
                   slow (outer tier) to fast (minor tier) — to reduce over.
    ``collective`` psum: lowering kind (:data:`COLLECTIVE_KINDS`).
    ``groups``     psum: static device count per axis in ``axis`` (tuple,
                   same order).  Optional; lets the reduce-scatter lowering
                   check tiling divisibility at trace time.
    ``tile_map``   gemv: per-tile *effective* storage levels (a
                   :class:`repro.core.precision.TileMap`, already min'd
                   against the stage level) quantizing the operand tiles —
                   tile-centric mixed precision, DESIGN.md §8.  On sharded
                   runs the map's grid partitions the *local* operand
                   shard element-wise.
    ``comm``       gemv_psum: the fused reduction's level (what a separate
                   psum stage would carry as ``level``; the super-stage's
                   own ``level`` is the gemv compute level).
    ``body``       gemv_psum: the stages between the chunked gemv and its
                   reduction (reorder/ifft/unpad for the matvec tail;
                   empty for the Gram mid-reduction), executed per chunk.
                   A tuple of frozen stages, so the super-stage stays
                   hashable/jit-static.
    """

    kind: str
    level: str
    adjoint: bool = False
    to_tosi: bool = True
    operand: str = "F"
    axis: Union[str, Tuple[str, ...], None] = None
    collective: str = "psum"
    groups: Optional[Tuple[int, ...]] = None
    tile_map: Optional[prec.TileMap] = None
    comm: Optional[str] = None
    body: Tuple["Stage", ...] = ()

    def __post_init__(self):
        if self.kind not in STAGE_KINDS:
            raise ValueError(f"unknown stage kind {self.kind!r}")
        if self.level not in ("h", "s", "d"):
            raise ValueError(f"bad precision level {self.level!r}")
        if self.collective not in COLLECTIVE_KINDS:
            raise ValueError(f"unknown collective kind {self.collective!r}")
        if self.groups is not None and len(self.groups) != len(self.axes):
            raise ValueError("groups must match the psum axis tuple")
        if self.kind == "gemv_psum" and self.axis is None:
            raise ValueError("gemv_psum needs a psum axis — use a plain "
                             "gemv stage when there is no collective")

    @property
    def axes(self) -> Tuple[str, ...]:
        """The psum axis group as a tuple (slow -> fast order)."""
        if self.axis is None:
            return ()
        return (self.axis,) if isinstance(self.axis, str) else self.axis

    # -- gemv_psum expansion -------------------------------------------------
    def gemv_stage(self) -> "Stage":
        """The compute half of a gemv_psum super-stage."""
        return Stage("gemv", self.level, adjoint=self.adjoint,
                     operand=self.operand, tile_map=self.tile_map)

    def psum_stage(self) -> "Stage":
        """The reduction half of a gemv_psum super-stage."""
        return Stage("psum", self.comm or self.level, axis=self.axis,
                     collective=self.collective, groups=self.groups)


Plan = Tuple[Stage, ...]


# ---------------------------------------------------------------------------
# Stage implementations.  Carrier convention: time-domain data is a single
# real array of stacked SOTI rows (S*R, T); Fourier-domain data is a split
# (re, im) plane pair, SOTI (S*R, K) before/after the reorders and TOSI
# (K, R[, S]) between them.
# ---------------------------------------------------------------------------

def reorder_planes(re, im, level: str, *, to_tosi: bool, S: int = 1):
    """The SOTI<->TOSI reorder, parameterized over direction and RHS count.

    S = 1: a plain transpose (R, K) <-> (K, R), the paper's "purely memory"
    intermediate phase.  S > 1: stacked SOTI planes (S*R, K) <-> TOSI panels
    (K, R, S) with the RHS axis minor.  Runs at the lower of the adjacent
    compute phases' levels (the cast fuses with the copy).
    """
    dt = prec.real_dtype(level)
    if S == 1:
        return re.astype(dt).T, im.astype(dt).T
    if to_tosi:
        SR, K = re.shape
        R = SR // S
        f = lambda x: x.astype(dt).reshape(S, R, K).transpose(2, 1, 0)
    else:
        f = lambda x: x.astype(dt).transpose(2, 1, 0).reshape(-1, x.shape[0])
    return f(re), f(im)


def _pad(stage, x, operands, N_t, S, opts):
    return kops.pad_cast(x, 2 * N_t, prec.real_dtype(stage.level),
                         backend=opts.spec, dispatch=opts.table,
                         fuse=opts.fuse_pad_cast)


def _fft(stage, x, operands, N_t, S, opts):
    # batched rfft over the minor (time) axis; computes at >= f32 (complex
    # lives only inside the stage), stores split planes at the fft level
    lvl = stage.level
    v_hat = jnp.fft.rfft(x.astype(prec.fft_compute_dtype(lvl)), axis=-1)
    dt = prec.real_dtype(lvl)
    return v_hat.real.astype(dt), v_hat.imag.astype(dt)


def _reorder(stage, x, operands, N_t, S, opts):
    re, im = x
    return reorder_planes(re, im, stage.level, to_tosi=stage.to_tosi, S=S)


def _gemv(stage, x, operands, N_t, S, opts):
    # Fourier-space block-diagonal product: per frequency bin k an
    # (m x n) x (n[, S]) contraction — SBGEMV for one RHS, SBGEMM for a
    # stacked block.  ``operand`` selects F_hat or the precomputed Gram
    # blocks G_hat (the fused Hessian path).
    A_re, A_im = operands[stage.operand]
    dt = prec.real_dtype(stage.level)
    mode = "H" if stage.adjoint else "N"
    x_re, x_im = (p.astype(dt) for p in x)
    # stage-level dispatch: a forced-Pallas preference relaxes to auto for
    # levels the backend's Pallas cannot run (d stages of the paper ladder
    # on the CPU interpreter keep flowing through XLA)
    table = opts.table.for_dtype(dt, opts.spec)
    if S == 1:
        return kops.sbgemv(A_re.astype(dt), A_im.astype(dt), x_re, x_im,
                           mode, out_dtype=dt, backend=opts.spec,
                           dispatch=table, block_n=opts.block_n,
                           tile_map=stage.tile_map)
    return kops.sbgemm(A_re.astype(dt), A_im.astype(dt), x_re, x_im, mode,
                       out_dtype=dt, backend=opts.spec, dispatch=table,
                       block_n=opts.block_n, block_s=opts.block_s,
                       tile_map=stage.tile_map)


def _ifft(stage, x, operands, N_t, S, opts):
    lvl = stage.level
    cdt = prec.complex_dtype(lvl)
    v_hat = x[0].astype(cdt) + 1j * x[1].astype(cdt)
    v = jnp.fft.irfft(v_hat, n=2 * N_t, axis=-1)
    return v.astype(prec.real_dtype(lvl))


def _mask(stage, x, operands, N_t, S, opts):
    # The inter-pipeline truncation (the P1^T P1 projector of the circulant
    # embedding) as ONE memory stage at ONE level: truncate + zero-extend,
    # replacing the composed path's unpad -> io-cast -> pad cast chain.
    # Implemented as slice+pad rather than a masked in-place update — XLA
    # lowers this measurably faster — through the same fused Pallas
    # pad/cast kernels as the boundary phases when enabled.
    dt = prec.real_dtype(stage.level)
    y = kops.unpad_cast(x, N_t, dt, backend=opts.spec, dispatch=opts.table,
                        fuse=opts.fuse_pad_cast)
    return kops.pad_cast(y, 2 * N_t, dt, backend=opts.spec,
                         dispatch=opts.table, fuse=opts.fuse_pad_cast)


def _unpad(stage, x, operands, N_t, S, opts):
    return kops.unpad_cast(x, N_t, prec.real_dtype(stage.level),
                           backend=opts.spec, dispatch=opts.table,
                           fuse=opts.fuse_pad_cast)


def _collective_count(stage) -> int:
    """How many collective launches this psum stage lowers to (per carrier
    plane) — what :func:`record_stages` reports as ``collective:*`` keys."""
    if stage.collective == "hierarchical":
        return len(stage.axes)
    if stage.collective == "reduce_scatter":
        # reduce-scatter + all-gather, plus one flat psum across the outer
        # tiers when the group spans several mesh axes
        return 2 + (1 if len(stage.axes) > 1 else 0)
    if stage.collective == "ring":
        # g-1 ppermute hops over the minor group, plus one flat psum
        # across the outer tiers when the group spans several mesh axes
        g = stage.groups[-1] if stage.groups else 1
        return max(1, (g - 1) + (1 if len(stage.axes) > 1 else 0))
    return 1


def _reduce_scatter_all_reduce(q, axes):
    """All-reduce as reduce-scatter + all-gather over the minor (fast)
    axis, with a flat psum across any outer tiers in between.  The caller
    has already checked that the leading carrier dim tiles over the minor
    group (and falls back to the flat psum when it does not)."""
    minor = axes[-1]
    q = jax.lax.psum_scatter(q, minor, scatter_dimension=0, tiled=True)
    if len(axes) > 1:
        q = jax.lax.psum(q, axes[:-1])
    return jax.lax.all_gather(q, minor, axis=0, tiled=True)


def ring_permutation(g: int) -> Tuple[Tuple[int, int], ...]:
    """The ring schedule over a group of ``g`` ranks: rank i forwards to
    rank (i + 1) mod g.  A valid ring is a single Hamiltonian cycle —
    every rank appears exactly once as a source and once as a
    destination, and following the edges from rank 0 visits all g ranks
    before returning.  :func:`_ring_all_reduce` builds its ``ppermute``
    hops from this one helper so the schedule is inspectable (and
    checkable) by :mod:`repro.analysis` instead of an inline literal."""
    return tuple((i, (i + 1) % g) for i in range(g))


def _ring_all_reduce(q, axes, groups):
    """All-reduce over the minor (fast) axis as an explicit ppermute ring:
    g-1 hops circulate the ORIGINAL local partials around the ring, then
    each device reduces the g collected parts locally in canonical
    origin-rank order 0..g-1, with a flat psum across any outer tiers.

    The canonical order is the invariant that keeps the chunked ring
    schedule row-partition-exact against the serial one (DESIGN.md §10):
    every row's sum runs over the same g contributions in the same rank
    order no matter how the rows were chunked — a classic *segmented*
    reduce-scatter ring would start each segment's accumulation at a
    different rank, making the order depend on a row's position in the
    buffer and breaking bit parity under re-chunking.  The price is
    bandwidth — each hop carries the full payload, (g-1)x vs the
    reduce-scatter ring's 2(g-1)/g — which is the right trade for the
    paper's latency-bound ~0.8 MB data-vector collectives (and exactly
    what ``calibrate_overlap`` measures rather than assumes)."""
    minor = axes[-1]
    g = groups[-1]
    perm = list(ring_permutation(g))
    parts, recv = [q], q
    for _ in range(g - 1):
        recv = jax.lax.ppermute(recv, minor, perm)
        parts.append(recv)
    # after s hops device i holds the partial that originated at rank
    # (i - s) mod g; summing origins 0..g-1 needs part (idx - o) mod g
    idx = jax.lax.axis_index(minor)
    stacked = jnp.stack(parts)
    acc = jax.lax.dynamic_index_in_dim(stacked, idx % g, axis=0,
                                       keepdims=False)
    for origin in range(1, g):
        acc = acc + jax.lax.dynamic_index_in_dim(
            stacked, (idx - origin) % g, axis=0, keepdims=False)
    if len(axes) > 1:
        acc = jax.lax.psum(acc, axes[:-1])
    return acc


def _psum(stage, x, operands, N_t, S, opts):
    # Mesh reduction at the stage's *communication* level (reduced-
    # precision comm is the survey's next lever once compute is mixed).
    # The carrier dtype is restored after the collective: the old code
    # left the carrier at the comm dtype, silently downgrading every
    # downstream stage whenever the comm level sat below the pipeline's
    # (DESIGN.md §5).  Works on either carrier: a plane pair reduces
    # plane-wise.
    axes = stage.axes
    comm_dt = prec.real_dtype(stage.level)
    minor_group = stage.groups[-1] if stage.groups else None
    lead = (x[0] if isinstance(x, tuple) else x).shape[0]
    rs_ok = (stage.collective == "reduce_scatter"
             and minor_group is not None and lead % minor_group == 0)
    ring_ok = stage.collective == "ring" and minor_group is not None

    def reduce_one(p):
        carrier_dt = p.dtype
        q = p.astype(comm_dt)
        if stage.collective == "hierarchical":
            # fast (minor) tier first, then outward — the executed form of
            # the paper's within-row-then-across-rows blocking
            for ax in reversed(axes):
                q = jax.lax.psum(q, ax)
        elif rs_ok:
            q = _reduce_scatter_all_reduce(q, axes)
        elif ring_ok:
            q = _ring_all_reduce(q, axes, stage.groups)
        else:
            q = jax.lax.psum(q, axes)
        return q.astype(carrier_dt)

    # a requested decomposition the carrier/plan cannot satisfy runs the
    # flat psum instead — and SAYS so: a mis-sized grid must be visible
    # in the instrumentation, not just silently slower
    fallback = ((stage.collective == "reduce_scatter" and not rs_ok)
                or (stage.collective == "ring" and not ring_ok))
    key = (f"collective:{stage.collective}:fallback" if fallback
           else f"collective:{stage.collective}")
    n_coll = 1 if fallback else _collective_count(stage)
    for counter in _active_counters:
        counter[key] += n_coll
    if isinstance(x, tuple):
        return tuple(reduce_one(p) for p in x)
    return reduce_one(x)


def _overlap_chunks(stage, rows: int, opts) -> int:
    """Resolve the chunk count of a pipelined super-stage at lowering time
    (DESIGN.md §9): the ``ExecOpts.overlap`` preference against the
    backend's dispatch table, the local output-row count, and the static
    reduction-group size.  A gemv carrying a tile map never chunks — the
    map's grid partitions the WHOLE local operand, and re-gridding per
    chunk would change the quantization (losing parity with the serial
    plan)."""
    if stage.tile_map is not None:
        return 1
    group = None
    if stage.groups is not None:
        group = 1
        for g in stage.groups:
            group *= g
    return opts.table.overlap_chunks(rows, group, opts.spec,
                                     prefer=opts.overlap)


def _chunk_bounds(rows: int, K: int):
    """K near-equal static (start, size) row chunks (empty chunks drop)."""
    base, rem = divmod(rows, K)
    bounds, start = [], 0
    for i in range(K):
        size = base + (1 if i < rem else 0)
        if size:
            bounds.append((start, size))
        start += size
    return bounds


def _assemble_chunks(pieces, rows: int, S: int):
    """Stitch per-chunk outputs back into the serial row order.

    Buffer reuse (the plan-lowering side of DESIGN.md §10's donation
    rule): chunks are joined with ONE ``concatenate`` per carrier plane.
    The earlier zeros + ``dynamic_update_slice`` chain paid a dead
    zero-fill of the full output (every row is overwritten by exactly one
    chunk) and serialized K dependent updates; a single concatenate has
    no fill to elide, gives XLA one fusible producer per plane, and still
    aliases into the donated output buffer under ``jitted(donate=...)``."""
    if len(pieces) == 1:
        return pieces[0]
    if isinstance(pieces[0], tuple):
        # plane-pair carrier: rows live on axis 1 (TOSI layout)
        return tuple(
            jnp.concatenate([piece[p] for piece in pieces], axis=1)
            for p in range(len(pieces[0])))
    # flat time-domain carrier (S*rows_chunk, T): the stacked layout is
    # S-major, so chunk rows interleave — join through an (S, rows, T) view
    T = pieces[0].shape[-1]
    parts = [piece.reshape(S, piece.shape[0] // S, T) for piece in pieces]
    return jnp.concatenate(parts, axis=1).reshape(S * rows, T)


def _gemv_psum(stage, x, operands, N_t, S, opts):
    # The pipelined gemv -> psum super-stage (DESIGN.md §9): the Phase-3
    # contraction splits along its OUTPUT rows axis into K chunks so chunk
    # k's reduction is in flight while chunk k+1 computes (XLA's async
    # collectives overlap them inside shard_map).  Rows are independent in
    # both the contraction and the elementwise reduction, so the chunked
    # schedule computes every row exactly as the serial plan does — parity
    # is row-partition-exact, not just to roundoff.
    A_re, A_im = operands[stage.operand]
    axis = 2 if stage.adjoint else 1         # the gemv's output-rows axis
    rows = A_re.shape[axis]
    K = min(_overlap_chunks(stage, rows, opts), rows)
    sub = (stage.gemv_stage(),) + stage.body + (stage.psum_stage(),)
    if K <= 1:
        # serial schedule: delegate to the constituent stages so the
        # instrumentation (gemv/psum/collective:* counts) matches the
        # unpipelined plan stage for stage
        return run_stages(sub, x, operands, N_t=N_t, opts=opts, S=S)
    explicit = stage.collective == "ring"
    label = "ring" if explicit else "pipelined"
    for counter in _active_counters:
        counter[f"collective:{label}:{K}"] += 1
    compute, reduction = sub[:-1], sub[-1:]
    pieces = []
    pending = None       # double-buffered slot: chunk k-1's unreduced carrier
    for start, size in _chunk_bounds(rows, K):
        chunk_ops = dict(operands)
        chunk_ops[stage.operand] = (
            jax.lax.slice_in_dim(A_re, start, start + size, axis=axis),
            jax.lax.slice_in_dim(A_im, start, start + size, axis=axis))
        if not explicit:
            # PR-8 schedule: issue each chunk's collective inline and rely
            # on XLA's async all-reduce to overlap it with the next gemv
            pieces.append(run_stages(sub, x, chunk_ops, N_t=N_t, opts=opts,
                                     S=S))
            continue
        # explicit software pipeline (DESIGN.md §10): run ONLY the compute
        # stages for this chunk, then drain the PREVIOUS chunk's deferred
        # ring reduction — program order inside shard_map pins chunk k's
        # ppermute hops between chunk k's and k+1's gemv issue, so an
        # in-order executor overlaps them by construction instead of by
        # scheduler luck.  The slot is double-buffered: at most one
        # unreduced carrier is live alongside the chunk being computed.
        produced = run_stages(compute, x, chunk_ops, N_t=N_t, opts=opts,
                              S=S)
        if pending is not None:
            pieces.append(run_stages(reduction, pending, operands,
                                     N_t=N_t, opts=opts, S=S))
        pending = produced
    if pending is not None:
        # the last chunk's reduction has nothing left to hide behind
        pieces.append(run_stages(reduction, pending, operands,
                                 N_t=N_t, opts=opts, S=S))
    return _assemble_chunks(pieces, rows, S)


_STAGE_IMPLS = {"pad": _pad, "fft": _fft, "reorder": _reorder, "gemv": _gemv,
                "ifft": _ifft, "mask": _mask, "unpad": _unpad, "psum": _psum,
                "gemv_psum": _gemv_psum}


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

_active_counters: list = []


@contextlib.contextmanager
def record_stages() -> Iterator[collections.Counter]:
    """Count stages as the executor runs them.

    Yields a ``Counter`` mapping stage kind -> executions.  Psum stages
    additionally report their collective launches under
    ``"collective:<kind>"`` keys (e.g. a two-stage hierarchical reduction
    counts 2 under ``"collective:hierarchical"``) — this is how the
    hierarchical lowering is verified rather than asserted.  Counting
    happens when the executor's Python loop runs — i.e. every call for
    eager pipelines, once per trace under ``jit`` — so tests run the
    operators un-jitted inside this context.
    """
    counter: collections.Counter = collections.Counter()
    _active_counters.append(counter)
    try:
        yield counter
    finally:
        _active_counters.remove(counter)


def stage_counts(plan: Plan) -> collections.Counter:
    """Static stage census of a plan: ``{kind: count}``.

    A ``gemv_psum`` super-stage counts under its own kind AND under each
    constituent kind (``gemv``, its body stages, ``psum``), so censuses
    of pipelined and serial plans agree on the constituent totals — the
    super-stage is a schedule change, not a work change."""
    counter: collections.Counter = collections.Counter()
    for stage in plan:
        counter[stage.kind] += 1
        if stage.kind == "gemv_psum":
            counter["gemv"] += 1
            for b in stage.body:
                counter[b.kind] += 1
            counter["psum"] += 1
    return counter


def _stage_levels(stage: Stage) -> Iterator[str]:
    """Every precision level a stage computes or communicates at."""
    yield stage.level
    if stage.comm is not None:
        yield stage.comm
    for b in stage.body:
        yield from _stage_levels(b)


def _check_levels(stages: Sequence[Stage], spec: BackendSpec) -> None:
    """A stage at a level off the backend's ladder (a "d" stage on a TPU:
    no f64 FFT, no f64 Pallas) is refused before lowering, never computed
    at a lower precision."""
    ladder = spec.ladder()
    bad = [s.kind for s in stages
           if any(lvl not in ladder for lvl in _stage_levels(s))]
    if bad:
        raise UnsupportedOnBackend(
            f"backend {spec.fingerprint()!r} has no f64 datapath: it runs "
            f"the levels {'/'.join(ladder)}, but the plan runs "
            f"{', '.join(bad)} off that ladder (e.g. use "
            f"PrecisionConfig.from_string('sssss'))")


def run_stages(stages: Sequence[Stage], x, operands: Mapping, *, N_t: int,
               opts, S: int = 1):
    """Fold ``x`` through ``stages`` (no layout promotion — see run_plan).

    ``opts`` is an :class:`ExecOpts` (resolved against the live backend
    here, at lowering time) or an already-resolved :class:`ResolvedOpts`.
    """
    opts = _resolved(opts)
    _check_levels(stages, opts.spec)
    for stage in stages:
        for counter in _active_counters:
            counter[stage.kind] += 1
        x = _STAGE_IMPLS[stage.kind](stage, x, operands, N_t, S, opts)
    return x


def run_plan(plan: Plan, x, operands: Mapping, *, N_t: int, opts):
    """Execute a compiled plan on a SOTI block vector.

    ``x`` is (R, N_t) for one right-hand side or (R, N_t, S) for a stacked
    block (RHS axis minor); blocks are flattened to (S*R, N_t) stacked
    planes so phases 1/2/4/5 share the single-RHS codepaths (and fused
    Pallas pad/cast kernels), with Phase 3 dispatching to SBGEMM.
    ``operands`` maps operand tags ("F", "G") to split (re, im) TOSI planes.
    """
    if x.ndim == 3:
        R, _, S = x.shape
        flat = x.transpose(2, 0, 1).reshape(S * R, N_t)
        y = run_stages(plan, flat, operands, N_t=N_t, opts=opts, S=S)
        R_out = y.shape[0] // S
        return y.reshape(S, R_out, N_t).transpose(1, 2, 0)
    return run_stages(plan, x, operands, N_t=N_t, opts=opts, S=1)


# ---------------------------------------------------------------------------
# Plan builders
# ---------------------------------------------------------------------------

def _psum_stage(level: str, axis, collective: str,
                groups: Optional[Tuple[int, ...]],
                comm_level: Optional[str]) -> Stage:
    return Stage("psum", comm_level or level, axis=axis,
                 collective=collective, groups=groups)


def _gemv_tiles(cfg: PrecisionConfig, operand: str = "F"):
    """The gemv stage's tile map: the config's, min'd against the gemv
    level.  Only the F operand carries one — the map is derived from
    F_hat's block norms and says nothing about precomputed G blocks."""
    if cfg.tiles is None or operand != "F":
        return None
    return prec.TileMap(cfg.tiles.effective(cfg.gemv))


def matvec_plan(cfg: PrecisionConfig, *, adjoint: bool = False,
                psum_axis=None, operand: str = "F",
                collective: str = "psum",
                psum_groups: Optional[Tuple[int, ...]] = None,
                comm_level: Optional[str] = None,
                pipelined: bool = True) -> Plan:
    """The 5-phase matvec pipeline as a plan (paper §2.4).

    Forward (``d = F m``) and adjoint (``m = F* d``) differ only in the
    gemv stage's conjugate-transpose flag; the distributed version appends
    a Psum stage over the mesh axis — or slow-to-fast axis *tuple* — the
    local contraction was partial in, lowered per ``collective``
    (:data:`COLLECTIVE_KINDS`) at ``comm_level`` (None = the reduce
    level).  ``psum_groups`` carries the static device count per axis.
    ``operand`` selects the planes the gemv stage contracts against (the
    circulant Gram plan is this same pipeline over the "G" blocks).

    With a collective stage present and ``pipelined=True`` (the default),
    the gemv and its reduction are emitted as ONE ``gemv_psum``
    super-stage whose body carries the tail stages between them — the
    pipelined-collective form (DESIGN.md §9).  Whether it actually chunks
    is decided at plan-lowering time from ``ExecOpts.overlap``;
    ``pipelined=False`` keeps the flat serial stage list (the parity
    reference).  Single-device plans (no ``psum_axis``) are identical
    either way.
    """
    head = [
        Stage("pad", cfg.pad),
        Stage("fft", cfg.fft),
        Stage("reorder", cfg.reorder_level("fft", "gemv"), to_tosi=True),
    ]
    gemv = Stage("gemv", cfg.gemv, adjoint=adjoint, operand=operand,
                 tile_map=_gemv_tiles(cfg, operand))
    tail = (
        Stage("reorder", cfg.reorder_level("gemv", "ifft"), to_tosi=False),
        Stage("ifft", cfg.ifft),
        Stage("unpad", cfg.reduce),
    )
    if psum_axis is None:
        return tuple(head) + (gemv,) + tail
    if pipelined:
        fused = Stage("gemv_psum", cfg.gemv, adjoint=adjoint,
                      operand=operand,
                      tile_map=_gemv_tiles(cfg, operand),
                      axis=psum_axis, collective=collective,
                      groups=psum_groups,
                      comm=comm_level or cfg.reduce, body=tail)
        return tuple(head) + (fused,)
    return tuple(head) + (gemv,) + tail + (
        _psum_stage(cfg.reduce, psum_axis, collective, psum_groups,
                    comm_level),)


def gram_plan(cfg: PrecisionConfig, *, space: str = "parameter",
              mode: str = "exact", mid_psum_axis=None, psum_axis=None,
              collective: str = "psum",
              mid_psum_groups: Optional[Tuple[int, ...]] = None,
              psum_groups: Optional[Tuple[int, ...]] = None,
              comm_level: Optional[str] = None,
              pipelined: bool = True) -> Plan:
    """The fused Fourier-domain Gram pipeline (Hessian actions, Remark 1).

    ``space="parameter"`` builds F*F (CGNR's normal operator),
    ``space="data"`` builds F F* (the data-space Hessian's Gram part).

    ``mode="exact"`` chains both per-bin GEMMs through ONE pipeline:
    pad -> FFT -> GEMM -> IFFT -> *mask* -> FFT -> GEMM^H -> IFFT -> unpad.
    The mask stage is the inter-operator truncation (the circulant
    embedding's P^T P projector) fused in place of the composed path's
    unpad -> cast -> pad round trip; the result matches the composed
    ``rmatvec(matvec(v))`` to roundoff.

    ``mode="circulant"`` applies the precomputed per-bin Gram blocks
    G_hat[k] (operand "G") in a single 5-phase pass — exactly half the
    FFT/IFFT and reorder stages of the composed path.  It computes the
    *periodic* (circulant) Gram: the classic circulant approximation of the
    Toeplitz normal operator, exact only up to the truncation wrap term —
    use it as a preconditioner or for screening, not where the composed
    operator's value is required.

    ``collective``/``comm_level``/``*_groups`` parameterize both Psum
    stages exactly as in :func:`matvec_plan` (the mid reduction defaults
    to the reorder level between the gemv it completes and the ifft).
    ``pipelined`` fuses each gemv with the reduction it feeds into a
    ``gemv_psum`` super-stage (DESIGN.md §9): the mid reduction sits
    directly after the first gemv (empty body), the final one carries the
    reorder/ifft/unpad tail.
    """
    if space not in ("parameter", "data"):
        raise ValueError(f"unknown gram space {space!r}")
    if mode == "circulant":
        # the matvec pipeline verbatim, contracting the per-bin G blocks
        return matvec_plan(cfg, psum_axis=psum_axis, operand="G",
                           collective=collective, psum_groups=psum_groups,
                           comm_level=comm_level, pipelined=pipelined)
    if mode != "exact":
        raise ValueError(f"unknown gram mode {mode!r}")
    # exact: parameter space runs F then F* (first gemv forward), data space
    # F* then F.  The mid psum completes the first contraction on a mesh.
    first_adjoint = space == "data"
    mid_level = cfg.reorder_level("gemv", "ifft")
    stages = [
        Stage("pad", cfg.pad),
        Stage("fft", cfg.fft),
        Stage("reorder", cfg.reorder_level("fft", "gemv"), to_tosi=True),
    ]
    if mid_psum_axis is not None and pipelined:
        stages.append(Stage("gemv_psum", cfg.gemv, adjoint=first_adjoint,
                            tile_map=_gemv_tiles(cfg), axis=mid_psum_axis,
                            collective=collective, groups=mid_psum_groups,
                            comm=comm_level or mid_level))
    else:
        stages.append(Stage("gemv", cfg.gemv, adjoint=first_adjoint,
                            tile_map=_gemv_tiles(cfg)))
        if mid_psum_axis is not None:
            stages.append(_psum_stage(mid_level, mid_psum_axis, collective,
                                      mid_psum_groups, comm_level))
    stages += [
        Stage("reorder", mid_level, to_tosi=False),
        Stage("ifft", cfg.ifft),
        Stage("mask", prec.min_level(cfg.ifft, cfg.fft)),
        Stage("fft", cfg.fft),
        Stage("reorder", cfg.reorder_level("fft", "gemv"), to_tosi=True),
    ]
    gemv2 = Stage("gemv", cfg.gemv, adjoint=not first_adjoint,
                  tile_map=_gemv_tiles(cfg))
    tail = (
        Stage("reorder", cfg.reorder_level("gemv", "ifft"), to_tosi=False),
        Stage("ifft", cfg.ifft),
        Stage("unpad", cfg.reduce),
    )
    if psum_axis is None:
        return tuple(stages) + (gemv2,) + tail
    if pipelined:
        fused = Stage("gemv_psum", cfg.gemv, adjoint=not first_adjoint,
                      tile_map=_gemv_tiles(cfg), axis=psum_axis,
                      collective=collective, groups=psum_groups,
                      comm=comm_level or cfg.reduce, body=tail)
        return tuple(stages) + (fused,)
    return tuple(stages) + (gemv2,) + tail + (
        _psum_stage(cfg.reduce, psum_axis, collective, psum_groups,
                    comm_level),)
