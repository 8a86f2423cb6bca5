"""FFTMatvec: the paper's 5-phase mixed-precision matvec pipeline (C1+C3).

Phases (paper §2.4), for ``d = F m``:

  1. broadcast + zero-pad the input block vector        (memory op)
  2. batched FFT  m -> m_hat                            (XLA FFT)
  3. block-diagonal matvec in Fourier space (SBGEMV)    (Pallas / XLA)
  4. batched IFFT d_hat -> d_padded
  5. unpad + reduction over the processor-grid rows

plus the SOTI<->TOSI reorders between phases 2-3 and 3-4, which are pure
memory ops executed at the *lower* of the adjacent phases' precisions
(paper footnote 8).  The adjoint ``m = F* d`` runs the same phases with a
conjugate-transpose SBGEMV and broadcast/reduce roles swapped.

Every variant of the pipeline — forward/adjoint, one or S stacked
right-hand sides, local or 2-D-mesh sharded, plain or Gram-fused — is
*compiled* to a :mod:`repro.core.pipeline` plan and executed by the shared
stage-graph executor; this module holds the public operator that builds
those plans.  Every phase's precision comes from a :class:`PrecisionConfig`;
casts are fused with the pad/unpad memory ops (``kernels.ops.pad_cast``).

Distribution (paper §2.4, §3.7): a 2-D ``(row, col)`` device grid; rows
shard N_d, cols shard N_m.  ``m`` lives sharded over cols / replicated
over rows; ``d`` sharded over rows / replicated over cols.  For the F
matvec the only collective is the Phase-5 ``psum`` over cols; for F* it is
the Phase-1 broadcast over cols (materialized by SPMD when the input is
not yet replicated) and a ``psum`` over rows.  Either side of the grid may
map to a *tuple* of mesh axes (slow -> fast order, e.g. cols =
``("data", "model")``); whenever the grid has more than one row the plans
emit the *hierarchical* collective form — staged per-tier reductions, the
executed version of the comm-aware blocking ``core.partition`` models —
and ``mesh="auto"`` picks the grid itself via :func:`choose_grid`
(``grid=paper_grid(p)`` is the documented override).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.backend import resolve_backend
from repro.jax_compat import shard_map
from . import pipeline
from . import precision as prec
from .partition import NetworkModel, choose_grid
from .pipeline import ExecOpts, reorder_planes  # noqa: F401  (public API)
from .precision import PrecisionConfig
from .toeplitz import fourier_block_column

AxisSpec = Union[str, Tuple[str, ...], None]


def _as_axes(axis: AxisSpec) -> Tuple[str, ...]:
    """Normalize an axis spec (name, tuple of names, None/()) to a tuple."""
    if axis is None or axis == ():
        return ()
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _axis_or_none(axis: AxisSpec) -> AxisSpec:
    """A PartitionSpec entry for a grid axis: None when the grid has none."""
    return axis if axis not in ((), None) else None


def _auto_mesh(p_shape: Tuple[int, int, int], row_axis, col_axis,
               devices=None, grid: Optional[Tuple[int, int]] = None,
               net: Optional[NetworkModel] = None) -> Mesh:
    """Build the comm-aware 2-D mesh for ``mesh="auto"``.

    ``devices`` is a device count, an explicit device sequence, or None
    (all local devices); ``grid`` pins (p_r, p_c) — pass
    ``partition.paper_grid(p)`` for the published Frontier grids — and
    defaults to :func:`choose_grid` under ``net`` (default
    :class:`NetworkModel`).
    """
    N_t, N_d, N_m = p_shape
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        devs = jax.devices()
        if devices > len(devs):
            raise ValueError(f"mesh='auto' asked for {devices} devices but "
                             f"only {len(devs)} are visible")
        devs = devs[:devices]
    else:
        devs = list(devices)
    p = len(devs)
    if grid is None:
        grid = choose_grid(p, N_t, N_d, N_m, net=net or NetworkModel())
    p_r, p_c = grid
    if p_r * p_c != p:
        raise ValueError(f"grid {p_r}x{p_c} does not tile {p} devices")
    if not (isinstance(row_axis, str) and isinstance(col_axis, str)):
        raise ValueError("mesh='auto' needs single row/col axis names")
    return Mesh(np.asarray(devs).reshape(p_r, p_c), (row_axis, col_axis))


def _setup(spec, precision: PrecisionConfig, mesh=None, row_axis="row",
           col_axis="col"):
    """Phase 0 as ``spec``'s operators run it, ``F_col -> (F_hat_re,
    F_hat_im)``: at the top of the backend's ladder (a TPU has no f64 FFT:
    there it is f32, whatever x64 says), stored at the gemv level and
    tile-padded as the backend's Phase 3 reads the planes.  On a mesh, a
    jitted program in which each device transforms its own (row, col)
    shard of F_col in place: F_hat is never assembled on one device."""
    setup = functools.partial(
        fourier_block_column, dtype=prec.real_dtype(precision.gemv),
        compute_dtype=spec.setup_dtype, tile=spec.plane_tile)
    if mesh is None:
        return setup
    parts = P(None, _axis_or_none(row_axis), _axis_or_none(col_axis))
    return jax.jit(shard_map(setup, mesh=mesh, in_specs=parts,
                             out_specs=(parts, parts)))


def stored_planes(F_re, F_im, dims=None, grid=(1, 1)):
    """The (K, N_d, N_m) planes of stored ones, ``dims`` = (N_d, N_m).
    Where set-up stored them tile-padded (:attr:`BackendSpec.plane_tile`),
    each block of the (p_r, p_c) ``grid`` holds its (N_d / p_r, N_m / p_c)
    part at its leading corner.  On one block, inside a jitted program on
    the TPU, the slice is a bitcast of the stored buffers, which the
    Phase-3 kernel reads in place (DESIGN.md §12)."""
    if dims is None or F_re.shape[1:] == tuple(dims):
        return F_re, F_im
    N_d, N_m = dims
    if grid == (1, 1):
        return F_re[:, :N_d, :N_m], F_im[:, :N_d, :N_m]
    (p_r, p_c), (K, D, M) = grid, F_re.shape
    return tuple(F.reshape(K, p_r, D // p_r, p_c, M // p_c)
                 [:, :, :N_d // p_r, :, :N_m // p_c].reshape(K, N_d, N_m)
                 for F in (F_re, F_im))


# ---------------------------------------------------------------------------
# Local (per-shard) pipelines: plan construction + the shared executor.
# ---------------------------------------------------------------------------

def _local_matvec(F_re, F_im, m, N_t: int, cfg: PrecisionConfig,
                  opts: ExecOpts, adjoint: bool):
    """The per-shard 5-phase pipeline (no collectives).  ``m`` is the local
    SOTI input block vector; returns the local (partial) SOTI output at the
    reduce level."""
    plan = pipeline.matvec_plan(cfg, adjoint=adjoint)
    return pipeline.run_plan(plan, m, {"F": (F_re, F_im)}, N_t=N_t,
                             opts=opts)


def _local_matmat(F_re, F_im, M, N_t: int, cfg: PrecisionConfig,
                  opts: ExecOpts, adjoint: bool):
    """Multi-RHS per-shard pipeline.  ``M`` is (R, N_t, S): S stacked SOTI
    block vectors, RHS axis minor — same plan as the single-RHS case; the
    executor flattens the block so phases 1/2/4/5 reuse the single-RHS
    codepaths with S amortizing launch cost, and Phase 3 dispatches to the
    MXU-friendly SBGEMM."""
    return _local_matvec(F_re, F_im, M, N_t, cfg, opts, adjoint)


def _local_gram(F_re, F_im, v, N_t: int, cfg: PrecisionConfig,
                opts: ExecOpts, space: str = "parameter",
                mode: str = "exact", G_planes=None):
    """Per-shard fused Gram pipeline (F*F or F F*).  ``mode="circulant"``
    requires the precomputed per-bin Gram blocks in ``G_planes``."""
    plan = pipeline.gram_plan(cfg, space=space, mode=mode)
    operands = {"F": (F_re, F_im)}
    if G_planes is not None:
        operands["G"] = G_planes
    return pipeline.run_plan(plan, v, operands, N_t=N_t, opts=opts)


# ---------------------------------------------------------------------------
# Public operator
# ---------------------------------------------------------------------------

# A pytree whose leaves are the F_hat planes: a jitted function takes the
# operator as an argument, so the planes are inputs of the program and
# never constants baked into it (GBs at the paper shape).
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["F_hat_re", "F_hat_im"],
    meta_fields=["N_t", "precision", "opts", "mesh", "row_axis", "col_axis",
                 "comm_level", "collective", "dims"])
@dataclasses.dataclass
class FFTMatvec:
    """Block-triangular Toeplitz matvec operator.

    Single-device by default; pass ``mesh`` (+ axis names) for the 2-D
    processor-grid distributed version.  Input/output block vectors are in
    SOTI layout: ``m`` (N_m, N_t), ``d`` (N_d, N_t).  Multi-RHS blocks
    (``matmat``/``rmatmat``) stack S vectors along a minor axis:
    (R, N_t, S).  I/O dtype follows the paper: the working precision at
    entry/exit is the highest level in use (f64 in paper mode, f32
    TPU-native).

    All four entry points (matvec/rmatvec/matmat/rmatmat) — and the fused
    Gram operator returned by :meth:`gram` — compile to
    :mod:`repro.core.pipeline` plans and run through its shared executor;
    the mesh paths wrap the same plan (plus Psum stages) in ``shard_map``.
    """

    F_hat_re: jax.Array          # (K, N_d, N_m) TOSI, maybe tile-padded
    F_hat_im: jax.Array
    N_t: int
    precision: PrecisionConfig = PrecisionConfig()
    opts: ExecOpts = ExecOpts()
    mesh: Optional[Mesh] = None
    row_axis: AxisSpec = "row"
    col_axis: AxisSpec = "col"
    comm_level: Optional[str] = None     # reduction precision (None = reduce)
    collective: Optional[str] = None     # pipeline.COLLECTIVE_KINDS override
    dims: Optional[Tuple[int, int]] = None   # (N_d, N_m); None: planes' own

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_block_column(cls, F_col, precision=PrecisionConfig(),
                          opts=ExecOpts(), mesh=None,
                          row_axis="row", col_axis="col",
                          backend=None, devices=None, grid=None, net=None,
                          comm_level=None, collective=None) -> "FFTMatvec":
        """Phase-0 setup (always at the highest precision, paper §3.2.1),
        storing F_hat at the gemv level.  ``backend`` is a convenience
        override folded into ``opts`` (a spec or a registered name such
        as ``"xla-ref"``).

        ``mesh`` is a 2-D device mesh, or ``"auto"``: consult
        :func:`repro.core.choose_grid` for the comm-aware (p_r, p_c) grid
        over ``devices`` (a count, a device sequence, or None = all local
        devices) under ``net`` (default :class:`NetworkModel`), with
        ``grid`` — e.g. ``paper_grid(p)`` — as the documented override.
        ``row_axis``/``col_axis`` may be mesh-axis *tuples* (slow -> fast);
        ``comm_level`` runs the mesh reductions at a reduced precision
        (one rounding per reduction, carrier dtype restored — DESIGN.md
        §5) and ``collective`` pins the lowering (default: hierarchical
        whenever the grid has more than one row)."""
        if backend is not None:
            opts = dataclasses.replace(opts, backend=backend)
        if isinstance(mesh, str):
            if mesh != "auto":
                raise ValueError(f"unknown mesh spec {mesh!r}")
            mesh = _auto_mesh(F_col.shape, row_axis, col_axis,
                              devices=devices, grid=grid, net=net)
        F_re, F_im = _setup(resolve_backend(opts.backend), precision, mesh,
                            row_axis, col_axis)(F_col)
        return cls(F_re, F_im, F_col.shape[0], precision, opts, mesh,
                   row_axis, col_axis, comm_level, collective,
                   tuple(F_col.shape[1:]))

    def with_precision(self, precision: PrecisionConfig) -> "FFTMatvec":
        """Same operator retuned to another per-phase config.

        The stored Fourier blocks are recast to the new gemv level.  Casts
        preserve sharding; note an *upcast* cannot restore bits lost when
        the operator was originally stored low — retune from the
        highest-precision operator (``autotune`` does)."""
        dt = prec.real_dtype(precision.gemv)
        return dataclasses.replace(self, precision=precision,
                                   F_hat_re=self.F_hat_re.astype(dt),
                                   F_hat_im=self.F_hat_im.astype(dt))

    def with_backend(self, backend, dispatch=None) -> "FFTMatvec":
        """Same operator lowered through another backend (a
        :class:`repro.backend.BackendSpec` or registered name) and,
        optionally, another dispatch table.  Numerics are unchanged to
        roundoff — backends select lowerings, not semantics."""
        opts = dataclasses.replace(self.opts, backend=backend)
        if dispatch is not None:
            opts = dataclasses.replace(opts, dispatch=dispatch)
        return dataclasses.replace(self, opts=opts)

    def with_comm(self, comm_level: Optional[str],
                  collective: Optional[str] = None) -> "FFTMatvec":
        """Same operator with another communication precision and,
        optionally, another collective lowering (``"psum"`` /
        ``"hierarchical"`` / ``"reduce_scatter"`` / ``"ring"`` — the last
        is the explicit software-pipelined ppermute ring, DESIGN.md §10).
        ``comm_level=None`` restores the default (reductions at the
        reduce level)."""
        return dataclasses.replace(
            self, comm_level=comm_level,
            collective=self.collective if collective is None else collective)

    def with_overlap(self, overlap) -> "FFTMatvec":
        """Same operator with another pipelined-collective preference
        (``ExecOpts.overlap``, DESIGN.md §9): ``"auto"`` lets the dispatch
        table decide per backend, an int pins the chunk count, ``None``
        pins the serial schedule.  Overlap changes the timing of a plan,
        never its math."""
        return dataclasses.replace(
            self, opts=dataclasses.replace(self.opts, overlap=overlap))

    def autotune(self, tol: float, *, full_result: bool = False, **kw):
        """Dynamic mixed-precision selection (paper §3.2 at runtime).

        Picks the fastest per-phase config whose measured error stays
        within ``tol`` — pruning the lattice with the calibrated eq.-(6)
        model so only a small frontier is timed — and returns the
        operator retuned to it.  ``full_result=True`` returns the
        :class:`repro.tune.TuneResult` instead (records, Pareto front,
        bounds, measurement counts).  Keywords are forwarded to
        :func:`repro.tune.autotune` (``ladder``, ``variant`` — including
        ``"gram"`` for the fused Hessian pipeline —, ``cache``/
        ``cache_path``, ``repeats``, ``mode``, ...)."""
        from repro.tune import autotune as _autotune   # deferred: tune builds on core
        res = _autotune(self, tol=tol, **kw)
        return res if full_result else res.op

    def gram(self, space: str = "parameter", mode: str = "exact"):
        """The fused Fourier-domain Gram operator (see
        :class:`repro.core.gram.GramOperator`).

        ``space="parameter"`` -> F*F (CGNR's normal operator);
        ``space="data"`` -> F F* (the data-space Hessian's Gram part).
        ``mode="exact"`` matches the composed ``rmatvec(matvec(v))`` to
        roundoff in one fused pipeline; ``mode="circulant"`` applies the
        precomputed per-bin blocks G_hat[k] in a single 5-phase pass —
        half the FFT/reorder work, periodic-Gram semantics."""
        from .gram import GramOperator  # deferred: gram builds on this class
        return GramOperator.from_matvec(self, space=space, mode=mode)

    # -- shapes --------------------------------------------------------------
    @property
    def N_d(self) -> int:
        return (self.dims or self.F_hat_re.shape[1:])[0]

    @property
    def N_m(self) -> int:
        return (self.dims or self.F_hat_re.shape[1:])[1]

    @property
    def planes(self):
        """The (K, N_d, N_m) F_hat planes (see :func:`stored_planes`)."""
        return stored_planes(self.F_hat_re, self.F_hat_im, self.dims,
                             self.grid_shape())

    def local_planes(self, F_re, F_im):
        """One device's (K, N_d / p_r, N_m / p_c) planes, from its stored
        block (inside ``shard_map``)."""
        p_r, p_c = self.grid_shape()
        dims = self.dims and (self.dims[0] // p_r, self.dims[1] // p_c)
        return stored_planes(F_re, F_im, dims)

    @property
    def io_dtype(self):
        return prec.real_dtype(self.precision.highest())

    @property
    def _row(self):
        """Row axis spec (None for the paper's p_r = 1 regime)."""
        return _axis_or_none(self.row_axis)

    @property
    def _col(self):
        return _axis_or_none(self.col_axis)

    def grid_shape(self) -> tuple[int, int]:
        """(p_r, p_c) of the mesh grid — (1, 1) when single-device.

        A named row/col axis the mesh does not have is a construction
        error, surfaced here (bound pricing and collective selection both
        read this) rather than as a late shard_map KeyError — or, worse,
        a silently flat grid."""
        if self.mesh is None:
            return (1, 1)
        sizes = self.mesh.shape
        for a in (*_as_axes(self.row_axis), *_as_axes(self.col_axis)):
            if a not in sizes:
                raise ValueError(f"grid axis {a!r} is not a mesh axis "
                                 f"(mesh has {tuple(sizes)})")
        p_r = math.prod(sizes[a] for a in _as_axes(self.row_axis))
        p_c = math.prod(sizes[a] for a in _as_axes(self.col_axis))
        return (max(p_r, 1), max(p_c, 1))

    def _collective_kind(self, psum_axes: Tuple[str, ...],
                         adjoint: bool = False) -> str:
        """The emitted collective lowering, direction-aware.

        Forward (F): the explicit override, else hierarchical whenever the
        grid has > 1 row (the paper's comm-aware regime) or the reduction
        group spans several mesh tiers.  Adjoint (F*): the reduction runs
        over the *row* axis group first, so a single-axis row group has no
        inner tier to stage through — the hierarchical form there only
        serializes the flat reduction behind extra regrouping (the
        BENCH_fig4 rmatvec regression) and is emitted only when the row
        group itself spans several mesh axes."""
        if self.collective is not None:
            return self.collective
        if adjoint:
            return "hierarchical" if len(psum_axes) > 1 else "psum"
        p_r, _ = self.grid_shape()
        return "hierarchical" if (p_r > 1 or len(psum_axes) > 1) else "psum"

    def _psum_args(self, adjoint: bool) -> dict:
        """psum stage parameters for one matvec plan on this mesh."""
        psum_axes = _as_axes(self.row_axis if adjoint else self.col_axis)
        if not psum_axes:
            return {"psum_axis": None}
        sizes = self.mesh.shape
        return {"psum_axis": psum_axes[0] if len(psum_axes) == 1
                else psum_axes,
                "psum_groups": tuple(sizes[a] for a in psum_axes),
                "collective": self._collective_kind(psum_axes, adjoint),
                "comm_level": self.comm_level}

    # -- plan inspection --------------------------------------------------------
    def plan(self, *, adjoint: bool = False) -> pipeline.Plan:
        """The compiled matvec plan this operator executes: the
        single-device stage list, or — on a mesh — the same plan plus its
        collective stage (axes, static group sizes, collective kind and
        comm level all bound).  This is exactly what :meth:`matvec` /
        :meth:`rmatvec` run, exposed for stage-count verification and the
        :mod:`repro.analysis` linter."""
        if self.mesh is None:
            return pipeline.matvec_plan(self.precision, adjoint=adjoint)
        return pipeline.matvec_plan(self.precision, adjoint=adjoint,
                                    **self._psum_args(adjoint))

    # -- the one apply path ----------------------------------------------------
    def _apply(self, x, *, adjoint: bool):
        """Run one compiled matvec plan — single-device directly, mesh via
        the same plan (plus its Psum stage) wrapped in ``shard_map``."""
        opts, N_t, io_dtype = self.opts, self.N_t, self.io_dtype
        plan = self.plan(adjoint=adjoint)
        if self.mesh is None:
            y = pipeline.run_plan(plan, x, {"F": self.planes}, N_t=N_t,
                                  opts=opts)
            return y.astype(io_dtype)

        row, col = self._row, self._col
        # F: input sharded over cols, reduce over cols, output over rows;
        # F*: roles swapped (psum over rows only when the grid has > 1 row).
        in_axis, out_axis = (row, col) if adjoint else (col, row)

        def body(F_re, F_im, x_loc):
            y = pipeline.run_plan(plan, x_loc,
                                  {"F": self.local_planes(F_re, F_im)},
                                  N_t=N_t, opts=opts)
            return y.astype(io_dtype)

        tail = (None,) * (x.ndim - 1)
        return shard_map(
            body, mesh=self.mesh,
            in_specs=(P(None, row, col), P(None, row, col),
                      P(in_axis, *tail)),
            out_specs=P(out_axis, *tail),
        )(self.F_hat_re, self.F_hat_im, x)

    # -- public API ------------------------------------------------------------
    def matvec(self, m):
        """d = F m.   m: (N_m, N_t) SOTI -> d: (N_d, N_t) SOTI."""
        return self._apply(m, adjoint=False)

    def rmatvec(self, d):
        """m = F* d.  d: (N_d, N_t) SOTI -> m: (N_m, N_t) SOTI."""
        return self._apply(d, adjoint=True)

    def matmat(self, M):
        """D = F M over S stacked right-hand sides.

        M: (N_m, N_t, S) -> D: (N_d, N_t, S), RHS axis minor.  A 2-D input
        is promoted to S = 1 and squeezed back, so ``matvec`` is exactly
        the S = 1 special case of this method.
        """
        if M.ndim == 2:
            return self.matmat(M[..., None])[..., 0]
        return self._apply(M, adjoint=False)

    def rmatmat(self, D):
        """M = F* D over S stacked right-hand sides.
        D: (N_d, N_t, S) -> M: (N_m, N_t, S)."""
        if D.ndim == 2:
            return self.rmatmat(D[..., None])[..., 0]
        return self._apply(D, adjoint=True)

    def jitted(self, donate: bool = False):
        """Jit-compiled (matvec, rmatvec) pair.

        ``donate=True`` donates the input block vector's buffer to the
        computation (``donate_argnums``): with the pipelined super-stage's
        chunked writes this lets XLA reuse the input allocation for the
        assembled output instead of holding both live — the caller must
        not reuse the argument afterwards."""
        dn = (1,) if donate else ()
        return tuple(functools.partial(jax.jit(f, donate_argnums=dn), self)
                     for f in (FFTMatvec.matvec, FFTMatvec.rmatvec))

    def jitted_block(self, donate: bool = False):
        """Jit-compiled (matmat, rmatmat) pair (``donate`` as in
        :meth:`jitted`)."""
        dn = (1,) if donate else ()
        return tuple(functools.partial(jax.jit(f, donate_argnums=dn), self)
                     for f in (FFTMatvec.matmat, FFTMatvec.rmatmat))

    # -- sharding helpers -------------------------------------------------------
    def m_sharding(self, stacked: bool = False):
        assert self.mesh is not None
        spec = (P(self.col_axis, None, None) if stacked
                else P(self.col_axis, None))
        return NamedSharding(self.mesh, spec)

    def d_sharding(self, stacked: bool = False):
        assert self.mesh is not None
        spec = P(self._row, None, None) if stacked else P(self._row, None)
        return NamedSharding(self.mesh, spec)


# ---------------------------------------------------------------------------
# Per-phase callables for the runtime-breakdown benchmark (paper Fig. 2)
# ---------------------------------------------------------------------------

def phase_callables(op: FFTMatvec, adjoint: bool = False):
    """Separately jitted per-phase functions, keyed by the paper's phase
    names, each consuming the previous phase's output.  Slices the compiled
    plan into phase groups (the reorders time with the gemv they wrap,
    matching the paper's breakdown)."""
    plan = pipeline.matvec_plan(op.precision, adjoint=adjoint)
    N_t, opts, io_dtype, dims = op.N_t, op.opts, op.io_dtype, op.dims
    # group by stage kind (reorders attach to the gemv they wrap), robust
    # to the plan's exact stage order
    group_of = {"pad": "pad", "fft": "fft", "reorder": "gemv",
                "gemv": "gemv", "ifft": "ifft", "unpad": "reduce"}
    groups = {name: tuple(s for s in plan if group_of[s.kind] == name)
              for name in ("pad", "fft", "gemv", "ifft", "reduce")}

    def make(stages, final: bool):
        def f(F_re, F_im, x):      # the planes as arguments, not constants
            y = pipeline.run_stages(stages, x,
                                    {"F": stored_planes(F_re, F_im, dims)},
                                    N_t=N_t, opts=opts)
            return y.astype(io_dtype) if final else y
        return functools.partial(jax.jit(f), op.F_hat_re, op.F_hat_im)

    return {name: make(stages, final=(name == "reduce"))
            for name, stages in groups.items()}
