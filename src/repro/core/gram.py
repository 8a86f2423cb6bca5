"""Fused Fourier-domain Gram operators: F*F and F F* in one pipeline.

The paper's motivating outer loop (Remark 1, Bayesian OED) is dominated by
Hessian actions ``F G_pr F* v``: the composed implementation runs the full
adjoint pipeline back to the time domain and then the full forward pipeline
— paying an unpad -> cast -> pad round trip between them and exiting to the
I/O precision twice.  :class:`GramOperator` compiles the whole Gram action
to ONE :mod:`repro.core.pipeline` plan instead.

Two modes, with different exactness/cost trades:

``mode="exact"`` (default)
    pad -> FFT -> GEMM(F_hat) -> IFFT -> mask -> FFT -> GEMM(F_hat^H) ->
    IFFT -> unpad.  The mask stage applies the inter-operator truncation
    (the circulant embedding's P^T P projector) in place, fusing the
    composed path's unpad/pad/cast round trip; the result matches
    ``rmatvec(matvec(v))`` to roundoff.  This is what the Hessian and CGNR
    paths use.

``mode="circulant"``
    pad -> FFT -> per-bin GEMM with the precomputed Hermitian blocks
    G_hat[k] = F_hat[k]^H F_hat[k] (or the data-space twin
    F_hat[k] F_hat[k]^H) -> IFFT -> unpad.  Exactly HALF the FFT/IFFT and
    reorder stages of the composed path.  It computes the *periodic*
    (circulant) Gram: the restriction of C^H C rather than of C^H P^T P C,
    i.e. the classic circulant approximation of the Toeplitz normal
    operator (Strang/Chan-style).  The truncation wrap term it drops is
    O(1) in general, so use it where periodic semantics are acceptable —
    as a CG preconditioner or an OED screening proxy — never where the
    composed operator's value is required.

Both modes run on 2-D meshes through the same plan wrapped in
``shard_map`` for the exact mode (circulant precompute needs a cross-shard
contraction and stays single-device).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.jax_compat import shard_map
from repro.kernels import ops as kops
from . import pipeline
from . import precision as prec
from .fftmatvec import FFTMatvec, _as_axes
from .precision import PrecisionConfig


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["op", "G_hat_re", "G_hat_im"],
                   meta_fields=["space", "mode"])
@dataclasses.dataclass
class GramOperator:
    """One-pipeline Gram action, built by :meth:`FFTMatvec.gram`.

    ``space="parameter"``: G = F*F, acting on (N_m, N_t[, S]) SOTI blocks
    (CGNR's normal operator).  ``space="data"``: G = F F*, acting on
    (N_d, N_t[, S]) (the data-space Hessian's Gram part).
    """

    op: FFTMatvec
    space: str = "parameter"
    mode: str = "exact"
    G_hat_re: Optional[jax.Array] = None   # circulant mode: (K, R, R) planes
    G_hat_im: Optional[jax.Array] = None

    @classmethod
    def from_matvec(cls, op: FFTMatvec, *, space: str = "parameter",
                    mode: str = "exact") -> "GramOperator":
        if space not in ("parameter", "data"):
            raise ValueError(f"unknown gram space {space!r}")
        if mode not in ("exact", "circulant"):
            raise ValueError(f"unknown gram mode {mode!r}")
        G_re = G_im = None
        if mode == "circulant":
            if op.mesh is not None:
                raise NotImplementedError(
                    "circulant Gram precompute contracts over the sharded "
                    "operator axis; use mode='exact' on meshes")
            r = op.opts.resolve()
            dt = prec.real_dtype(op.precision.gemv)
            G_re, G_im = kops.sbgemm_gram(
                *op.planes, space=space, out_dtype=dt,
                backend=r.spec, dispatch=r.table.for_dtype(dt, r.spec),
                block_n=r.block_n)
        return cls(op, space, mode, G_re, G_im)

    # -- delegated operator identity -----------------------------------------
    @property
    def precision(self) -> PrecisionConfig:
        return self.op.precision

    @property
    def opts(self):
        return self.op.opts

    @property
    def mesh(self):
        return self.op.mesh

    @property
    def N_t(self) -> int:
        return self.op.N_t

    @property
    def N_d(self) -> int:
        return self.op.N_d

    @property
    def N_m(self) -> int:
        return self.op.N_m

    @property
    def io_dtype(self):
        return self.op.io_dtype

    @property
    def rows(self) -> int:
        """Row count of the (square) Gram's SOTI domain."""
        return self.N_m if self.space == "parameter" else self.N_d

    def with_precision(self, precision: PrecisionConfig) -> "GramOperator":
        """Gram of the retuned operator (circulant blocks recomputed at the
        new gemv level from the recast Fourier blocks)."""
        return self.from_matvec(self.op.with_precision(precision),
                                space=self.space, mode=self.mode)

    # -- plan inspection -------------------------------------------------------
    def _mesh_roles(self):
        """(io_axis, mid_axes, out_axes) of the mesh Gram pipeline."""
        op = self.op
        if self.space == "parameter":
            # F then F*: the forward GEMM is partial over cols (mid psum),
            # the adjoint GEMM partial over rows (final psum, p_r > 1 only).
            return op._col, _as_axes(op.col_axis), _as_axes(op.row_axis)
        # F* then F: roles swapped; the final psum over cols is always
        # needed, the mid one only when the grid has > 1 row.
        return op._row, _as_axes(op.row_axis), _as_axes(op.col_axis)

    def plan(self) -> pipeline.Plan:
        """The compiled stage plan this operator executes: single-device,
        or — on a mesh — the same pipeline with its mid and final
        collective stages bound (axes, static group sizes, collective
        kind and comm level).  Exactly what :meth:`apply` runs; exposed
        for stage-count verification and the :mod:`repro.analysis`
        linter."""
        if self.mesh is None:
            return pipeline.gram_plan(self.precision, space=self.space,
                                      mode=self.mode)
        op = self.op
        _, mid_axes, out_axes = self._mesh_roles()

        def axspec(axes):
            return None if not axes else \
                (axes[0] if len(axes) == 1 else axes)

        sizes = op.mesh.shape
        groups = lambda axes: tuple(sizes[a] for a in axes) or None
        widest = mid_axes if len(mid_axes) >= len(out_axes) else out_axes
        return pipeline.gram_plan(self.precision, space=self.space,
                                  mode=self.mode,
                                  mid_psum_axis=axspec(mid_axes),
                                  psum_axis=axspec(out_axes),
                                  mid_psum_groups=groups(mid_axes),
                                  psum_groups=groups(out_axes),
                                  collective=op._collective_kind(widest),
                                  comm_level=op.comm_level)

    def stage_counts(self):
        """Static stage census of :meth:`plan`."""
        return pipeline.stage_counts(self.plan())

    # -- application -------------------------------------------------------------
    def _operands(self, F_re, F_im):
        ops = {"F": (F_re, F_im)}
        if self.mode == "circulant":
            ops["G"] = (self.G_hat_re, self.G_hat_im)
        return ops

    def apply(self, v):
        """G v on an (rows, N_t[, S]) SOTI block; 2-D inputs squeeze back
        like :meth:`FFTMatvec.matmat`."""
        if self.mesh is None:
            plan = self.plan()
            y = pipeline.run_plan(plan, v, self._operands(*self.op.planes),
                                  N_t=self.N_t, opts=self.opts)
            return y.astype(self.io_dtype)

        op = self.op
        row, col = op._row, op._col
        io_axis, _, _ = self._mesh_roles()
        plan = self.plan()
        N_t, opts, io_dtype = self.N_t, self.opts, self.io_dtype
        operands = self._operands

        def body(F_re, F_im, v_loc):
            y = pipeline.run_plan(plan, v_loc,
                                  operands(*op.local_planes(F_re, F_im)),
                                  N_t=N_t, opts=opts)
            return y.astype(io_dtype)

        tail = (None,) * (v.ndim - 1)
        return shard_map(
            body, mesh=self.mesh,
            in_specs=(P(None, row, col), P(None, row, col),
                      P(io_axis, *tail)),
            out_specs=P(io_axis, *tail),
        )(op.F_hat_re, op.F_hat_im, v)

    __call__ = apply

    def jitted(self):
        """Jit-compiled apply (the operator's planes are arguments of the
        program, not constants)."""
        return functools.partial(jax.jit(GramOperator.apply), self)

    def v_sharding(self, stacked: bool = False):
        """Sharding of the Gram's in/out block vectors on the mesh."""
        assert self.mesh is not None
        axis = self.op.col_axis if self.space == "parameter" else self.op._row
        spec = P(axis, None, None) if stacked else P(axis, None)
        return NamedSharding(self.mesh, spec)
