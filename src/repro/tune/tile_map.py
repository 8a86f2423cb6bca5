"""Derive per-tile precision maps from block norms of F_hat (DESIGN.md §8).

The Toeplitz structure makes this analysis free at setup time: F_hat is
static per operator, so per-block norms of the spectrum are computed once
and the tile map they imply is a *static* compile-time artifact — no
runtime data inspection, no dynamic dispatch inside the kernels.

The derivation extends eq. (6) with a per-tile gemv term (see
:func:`repro.core.error_model.relative_error_bound`): the uniform config's
gemv error budget ``tol - (bound(cfg) - gemv_term(cfg))`` is split evenly
across the map's cells, and each cell independently takes the *lowest*
ladder level whose weighted contribution ``amp * c3 * w_t * n_local *
eps(level)`` fits its share.  Cells carrying little of the spectrum's
energy (small ``w_t``) can afford bf16; hot cells stay at the phase
level.  By construction the resulting tile-aware bound is <= ``tol`` —
and :func:`derive_tile_map` re-evaluates the bound to enforce it, and
returns None rather than a map that drops nothing below the uniform
level (no win) or misses tolerance.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.core import error_model
from repro.core.precision import (PrecisionConfig, TileMap, _LEVELS,
                                  machine_eps)


def block_norms(F_re, F_im=None, shape: tuple[int, int] = (2, 2)):
    """Per-cell Frobenius norms of the operand spectrum.

    ``F_re``/``F_im`` are the (K, N_d, N_m) split planes of F_hat (``F_im``
    None for a real operand).  The (R, C) grid partitions the frequency-bin
    axis K and the model axis N_m element-wise — exactly the partition the
    kernels quantize by (``kernels.ref.expand_tile_levels``).  Returns a
    numpy (R, C) float64 array.
    """
    R, C = shape
    mag = np.asarray(F_re, dtype=np.float64) ** 2
    if F_im is not None:
        mag = mag + np.asarray(F_im, dtype=np.float64) ** 2
    P = mag.sum(axis=1)                       # (K, n): energy per column
    K, n = P.shape
    rows = (np.arange(K) * R) // K
    cols = (np.arange(n) * C) // n
    out = np.zeros((R, C), dtype=np.float64)
    np.add.at(out, (rows[:, None], cols[None, :]), P)
    return np.sqrt(out)


def tile_weights(norms) -> tuple:
    """Energy fractions of the per-cell norms: ``||A_t||_F^2 / ||A||_F^2``.

    These are the ``w_t`` of the tile-aware eq.-(6) term — how much of the
    contraction mass each tile carries.  Nested tuple, rows summing to 1
    overall (uniform if the operand is identically zero).
    """
    sq = np.asarray(norms, dtype=np.float64) ** 2
    total = sq.sum()
    if total <= 0.0:
        sq = np.ones_like(sq)
        total = sq.sum()
    frac = sq / total
    return tuple(tuple(float(v) for v in row) for row in frac)


def derive_tile_map(cfg: PrecisionConfig, tol: float, N_t: int, N_d: int,
                    N_m: int, *, shape: tuple[int, int] = (2, 2),
                    weights: Optional[Sequence] = None,
                    p_r: int = 1, p_c: int = 1, adjoint: bool = False,
                    kappa: float = 1.0, input_level: str = "d",
                    constants: dict | None = None,
                    variant: str | None = None,
                    comm_level: str | None = None) -> Optional[TileMap]:
    """Lowest-precision tile map keeping the eq.-(6) bound within ``tol``.

    ``cfg`` is the (phase-uniform) base config; ``weights`` the per-cell
    block-norm fractions from :func:`tile_weights` (None = uniform).
    Returns None when no admissible map improves on the uniform config:
    the base config is already out of tolerance, no cell can drop below
    the gemv level, or the re-evaluated tile-aware bound misses ``tol``.
    """
    if cfg.tiles is not None:
        cfg = cfg.replace(tiles=None)
    bound_kw = dict(p_r=p_r, p_c=p_c, adjoint=adjoint, kappa=kappa,
                    input_level=input_level, constants=constants,
                    variant=variant, comm_level=comm_level)
    base = error_model.relative_error_bound(cfg, N_t, N_d, N_m, **bound_kw)
    if base > tol:
        return None

    R, C = shape
    w = error_model._normalized_weights(weights, (R, C))
    f = error_model.phase_factors(N_t, N_d, N_m, p_r, p_c, adjoint=adjoint,
                                  variant=variant)
    c = {"c3": 1.0}
    if constants:
        c.update({k: v for k, v in constants.items() if k == "c3"})
    amp = kappa ** 2 if variant in ("gram", "gram_data") else kappa
    gemv_coeff = amp * c["c3"] * f["gemv"]

    # the uniform config's gemv term is the budget we re-spend per tile
    budget_total = tol - (base - gemv_coeff * machine_eps(cfg.gemv))
    budget_cell = budget_total / (R * C)
    cells = []
    for wt in w:
        lvl = "d"       # effective min(d, gemv) = gemv: never worse
        for cand in _LEVELS:                 # low -> high
            if gemv_coeff * wt * machine_eps(cand) <= budget_cell:
                lvl = cand
                break
        cells.append(lvl)
    tiles = TileMap(tuple(tuple(cells[r * C:(r + 1) * C]) for r in range(R)))

    eff = tiles.effective(cfg.gemv)
    if all(l == cfg.gemv for row in eff for l in row):
        return None     # nothing drops below the uniform level: no win
    tiled = cfg.replace(tiles=tiles)
    if error_model.relative_error_bound(tiled, N_t, N_d, N_m,
                                        tile_weights=weights,
                                        **bound_kw) > tol:
        return None
    return tiles


def tile_map_for_operator(op, cfg: PrecisionConfig, tol: float, *,
                          shape: tuple[int, int] = (2, 2),
                          p_r: int = 1, p_c: int = 1,
                          adjoint: bool = False,
                          kappa: float = 1.0,
                          input_level: str = "d",
                          constants: dict | None = None,
                          variant: str | None = None,
                          comm_level: str | None = None):
    """Block-norm analysis + derivation for a live :class:`FFTMatvec`.

    Returns ``(tile_map_or_None, weights)`` — the weights are returned so
    the caller can evaluate the matching tile-aware bound (and thread them
    through ``prune_lattice``).
    """
    w = tile_weights(block_norms(*op.planes, shape))
    tiles = derive_tile_map(
        cfg, tol, op.N_t, op.N_d, op.N_m, shape=shape, weights=w,
        p_r=p_r, p_c=p_c, adjoint=adjoint, kappa=kappa,
        input_level=input_level, constants=constants, variant=variant,
        comm_level=comm_level)
    return tiles, w
