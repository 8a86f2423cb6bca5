"""PlanContext: a lowered plan plus everything needed to inspect it.

The context owns the *abstract trace*: :func:`jax.make_jaxpr` over
``ShapeDtypeStruct`` inputs runs the whole plan lowering — backend
dispatch, Pallas kernel construction, collective emission — without
allocating a single buffer or executing a single op, so linting the
paper-shape plans (N_m = 5000, K = 1001) is as cheap as linting the
smoke shapes.

Mesh plans trace the same way the distributed tests execute them
(``tests/test_overlap.py``): the plan body is wrapped in nested
``jax.vmap(..., axis_name=ax)`` so psum/ppermute bind against real named
axes.  Binding order follows the stage convention — axes are bound in
first-appearance (slow -> fast) order, which makes the *last-bound*
(minor) axis the outermost vmap; the dummy leading array dims therefore
carry the group sizes in reversed (fast -> slow) order.  Two tracing
caveats the rules must respect:

* traces always run under ``enable_x64`` — the lint judges the plan's
  *declared* dtype lattice, which an x64-disabled host process would
  silently clamp to f32 before any rule could see it;
* vmap batching rewrites collectives structurally (``ppermute``
  becomes a gather, ``psum_scatter`` a ``reduce_sum``), so rules must
  not key off collective primitive names in the trace — the executor's
  Python-side stage counters (recorded during tracing, exposed as
  :attr:`PlanContext.trace_counters`) are the reliable signal.

Derived shape conventions (see DESIGN.md §11):

* input rows follow the first contraction stage: ``N_m`` for a forward
  gemv, ``N_d`` for an adjoint one (``rows`` overrides, e.g. for the
  square circulant-Gram "G" operand);
* collective group sizes shard the dimension their gemv contracts over
  (a forward gemv's completing collective spans the col tiers, an
  adjoint one's the row tiers), so local operand planes are
  ``(K, N_d / p_r, N_m / p_c)`` exactly as under ``shard_map``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.experimental
import jax.numpy as jnp

from repro.core import pipeline
from repro.core import precision as prec
from repro.core.pipeline import ExecOpts, Plan, Stage

# Stage kinds whose ``level`` is a *data* (compute/storage) precision —
# a psum stage's level is the communication precision and the carrier
# dtype is restored after it (DESIGN.md §5), so it never sets the level
# of the value flowing past it.
DATA_KINDS = ("pad", "fft", "reorder", "gemv", "ifft", "mask", "unpad")


def expand(plan: Plan) -> Tuple[Tuple[Optional[int], Stage], ...]:
    """Flatten ``gemv_psum`` super-stages into their constituent
    (gemv, *body, psum) sequence, keeping each constituent tagged with
    the index of the plan stage it came from."""
    out = []
    for i, stage in enumerate(plan):
        if stage.kind == "gemv_psum":
            out.append((i, stage.gemv_stage()))
            out.extend((i, b) for b in stage.body)
            out.append((i, stage.psum_stage()))
        else:
            out.append((i, stage))
    return tuple(out)


def iter_eqns(jaxpr, path: str = "") -> Iterator[tuple]:
    """Yield ``(eqn, parent_jaxpr, path)`` for every equation, descending
    into sub-jaxprs carried in params (pjit bodies, scans, pallas_call
    kernels, custom_* rules, ...)."""
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}/{i}:{eqn.primitive.name}"
        yield eqn, jaxpr, here
        for v in eqn.params.values():
            subs = v if isinstance(v, (list, tuple)) else [v]
            for sub in subs:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner, here)
                elif hasattr(sub, "eqns"):
                    yield from iter_eqns(sub, here)


@dataclasses.dataclass
class PlanContext:
    """One plan bound to concrete dims/opts, with a lazily-built trace."""

    plan: Plan
    opts: ExecOpts
    N_t: int
    N_d: int
    N_m: int
    S: int = 1
    rows: Optional[int] = None        # input-rows override (square "G" plans)

    @classmethod
    def from_plan(cls, plan: Plan, opts: Optional[ExecOpts] = None, *,
                  N_t: int, N_d: int, N_m: int, S: int = 1,
                  rows: Optional[int] = None) -> "PlanContext":
        return cls(tuple(plan), opts if opts is not None else ExecOpts(),
                   N_t, N_d, N_m, S, rows)

    # -- static structure ---------------------------------------------------
    @functools.cached_property
    def expanded(self):
        return expand(self.plan)

    def stages(self, *kinds) -> Tuple[Tuple[Optional[int], Stage], ...]:
        return tuple((i, s) for i, s in self.expanded
                     if not kinds or s.kind in kinds)

    @functools.cached_property
    def axis_sizes(self) -> Dict[str, int]:
        """Mesh axis name -> static group size, from the collective
        stages' ``groups``.  An axis named without a static group size
        binds at size 1 (the collective still traces; group-dependent
        lowerings surface their fallback — see the invariants pass)."""
        sizes: Dict[str, int] = {}
        for _, s in self.expanded:
            groups = s.groups or (1,) * len(s.axes)
            for ax, g in zip(s.axes, groups):
                sizes[ax] = max(sizes.get(ax, 1), g)
        return sizes

    @functools.cached_property
    def bound_axes(self) -> Tuple[str, ...]:
        """All collective axis names in first-appearance slow -> fast
        order — the vmap binding order of the trace."""
        seen = []
        for _, s in self.expanded:
            for ax in s.axes:
                if ax not in seen:
                    seen.append(ax)
        return tuple(seen)

    @functools.cached_property
    def operand_tags(self) -> Tuple[str, ...]:
        tags = []
        for _, s in self.expanded:
            if s.kind == "gemv" and s.operand not in tags:
                tags.append(s.operand)
        return tuple(tags)

    def _gemv_level(self, tag: str) -> str:
        for _, s in self.expanded:
            if s.kind == "gemv" and s.operand == tag:
                return s.level
        return self.highest_level

    @functools.cached_property
    def _contraction_shards(self) -> Tuple[int, int]:
        """(row_shard, col_shard): how many ways N_d / N_m are split
        locally, from each collective's completing gemv direction."""
        row_p = col_p = 1
        last_adjoint = False
        for _, s in self.expanded:
            if s.kind == "gemv":
                last_adjoint = s.adjoint
            elif s.kind == "psum" and s.groups:
                g = 1
                for n in s.groups:
                    g *= n
                if last_adjoint:
                    row_p = max(row_p, g)
                else:
                    col_p = max(col_p, g)
        return row_p, col_p

    @property
    def N_d_local(self) -> int:
        return self.N_d // self._contraction_shards[0]

    @property
    def N_m_local(self) -> int:
        return self.N_m // self._contraction_shards[1]

    @functools.cached_property
    def input_rows(self) -> int:
        if self.rows is not None:
            return self.rows
        for _, s in self.expanded:
            if s.kind == "gemv":
                if s.operand != "F":
                    # square precomputed-block operand (circulant Gram)
                    return self.N_m_local
                return self.N_d_local if s.adjoint else self.N_m_local
        return self.N_m_local

    @functools.cached_property
    def highest_level(self) -> str:
        return prec.max_level([s.level for _, s in self.expanded])

    @functools.cached_property
    def declared_output_level(self) -> str:
        """The level of the last *data* stage — what the plan promises
        its output carrier runs at (psum stages restore the carrier, so
        a trailing reduction inherits its predecessor's level)."""
        for _, s in reversed(self.expanded):
            if s.kind in DATA_KINDS:
                return s.level
        return self.highest_level

    # -- the abstract trace --------------------------------------------------
    def _operand_specs(self, lead: Tuple[int, ...]):
        K = self.N_t + 1
        specs = []
        for tag in self.operand_tags:
            dt = prec.real_dtype(self._gemv_level(tag))
            if tag == "F":
                shape = lead + (K, self.N_d_local, self.N_m_local)
            else:
                shape = lead + (K, self.input_rows, self.input_rows)
            specs.append((tag, tuple(jax.ShapeDtypeStruct(shape, dt)
                                     for _ in range(2))))
        return specs

    @functools.cached_property
    def _trace(self):
        """(closed jaxpr, stage counters) — the plan traced abstractly
        (never executed) under ``enable_x64``, with the executor's
        Python-side counters recorded as tracing runs the stage loop."""
        plan, opts, N_t, S = self.plan, self.opts, self.N_t, self.S
        tags = self.operand_tags
        lead = tuple(self.axis_sizes[a] for a in reversed(self.bound_axes))
        io_dt = prec.real_dtype(self.highest_level)
        xshape = (self.input_rows, N_t) if S == 1 \
            else (self.input_rows, N_t, S)
        x = jax.ShapeDtypeStruct(lead + xshape, io_dt)
        specs = self._operand_specs(lead)
        planes = [p for _, pair in specs for p in pair]

        def f(x, *flat):
            operands, i = {}, 0
            for tag in tags:
                operands[tag] = (flat[i], flat[i + 1])
                i += 2
            return pipeline.run_plan(plan, x, operands, N_t=N_t, opts=opts)

        h = f
        for ax in self.bound_axes:     # bind slow first; minor ends outermost
            h = jax.vmap(h, axis_name=ax)
        with jax.enable_x64(True), \
                pipeline.record_stages() as counters:
            jx = jax.make_jaxpr(h)(x, *planes)
        return jx, collections.Counter(counters)

    @property
    def jaxpr(self):
        """The plan's closed jaxpr, traced abstractly (never executed)."""
        return self._trace[0]

    @property
    def trace_counters(self) -> collections.Counter:
        """Stage/collective counters the executor recorded while the
        abstract trace ran — the reliable collective signal (vmap
        batching erases collective primitives from the jaxpr itself)."""
        return self._trace[1]

    @property
    def out_avals(self):
        return self.jaxpr.out_avals

    def eqns(self) -> Iterator[tuple]:
        return iter_eqns(self.jaxpr.jaxpr)

    def trace_stage_group(self, stages: Tuple[Stage, ...], in_level: str):
        """Abstractly trace a stage subsequence on a dummy carrier at
        ``in_level`` — used by per-stage contract rules (e.g. "a psum
        stage restores the carrier dtype")."""
        opts, N_t = self.opts, self.N_t
        axes = []
        for s in stages:
            for ax in s.axes:
                if ax not in axes:
                    axes.append(ax)
        lead = tuple(self.axis_sizes.get(a, 1) for a in reversed(axes))
        rows = max(1, self.input_rows)
        x = jax.ShapeDtypeStruct(lead + (rows, 2 * N_t),
                                 prec.real_dtype(in_level))

        def f(x):
            return pipeline.run_stages(stages, x, {}, N_t=N_t, opts=opts)

        h = f
        for ax in axes:
            h = jax.vmap(h, axis_name=ax)
        with jax.enable_x64(True):
            return jax.make_jaxpr(h)(x)


def trace_callable(fn, *args):
    """``make_jaxpr`` convenience for callable-scoped lint rules: ``args``
    are arrays or ``ShapeDtypeStruct``s; nothing is executed."""
    return jax.make_jaxpr(fn)(*args)


def float_level(dtype) -> Optional[int]:
    """Index of a float dtype on the h < s < d ladder (None: not a
    ladder dtype — integers, bools, complex intermediates)."""
    table = {jnp.dtype(jnp.bfloat16): 0, jnp.dtype(jnp.float16): 0,
             jnp.dtype(jnp.float32): 1, jnp.dtype(jnp.float64): 2}
    return table.get(jnp.dtype(dtype))
