"""Per-phase precision configuration for the FFTMatvec pipeline (paper C3).

The paper lets each of the five computational phases run in FP64 ("d") or
FP32 ("s"); the 2^5 = 32 configurations are explored by a Pareto-front
analysis.  On TPU there is no native FP64 datapath, so we generalize to a
three-level ladder:

    "d" -> float64   (paper-faithful; CPU / validation only)
    "s" -> float32   (TPU high precision)
    "h" -> bfloat16  (TPU low precision)

A configuration is written exactly like the paper's runtime flag, e.g.
``-prec dssdd`` -> ``PrecisionConfig.from_string("dssdd")``.  Complex data
is carried as split re/im planes of the phase's *real* dtype (Pallas TPU
has no complex dtype; the MXU is a real systolic array) — see DESIGN.md §2.
A phase that stores at a lower rung than the value it is handed rounds it
with :func:`repro.kernels.rounding.store` at the rung's
:func:`real_dtype`: ``lax.reduce_precision``, never a cast there and back,
which the TPU compiler may drop (DESIGN.md §13).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, Optional, Sequence

import jax.numpy as jnp

PHASES = ("pad", "fft", "gemv", "ifft", "reduce")

_LEVELS = ("h", "s", "d")  # ordered low -> high
_REAL_DTYPE = {"d": jnp.float64, "s": jnp.float32, "h": jnp.bfloat16}
# FFTs always *compute* in >= f32 (XLA FFT op supports f32/f64 only; TPU FFTs
# are f32).  "h" phases compute f32 and store bf16 at phase boundaries, each
# store rounded by ``kernels.rounding.store`` (DESIGN.md §13).
_FFT_COMPUTE_DTYPE = {"d": jnp.float64, "s": jnp.float32, "h": jnp.float32}
_COMPLEX_DTYPE = {"d": jnp.complex128, "s": jnp.complex64, "h": jnp.complex64}

# Unit roundoff per level (bf16: 8 mantissa bits incl. implicit -> 2^-8).
MACHINE_EPS = {"d": 2.0 ** -53, "s": 2.0 ** -24, "h": 2.0 ** -8}


def real_dtype(level: str):
    return _REAL_DTYPE[level]


def fft_compute_dtype(level: str):
    return _FFT_COMPUTE_DTYPE[level]


def complex_dtype(level: str):
    return _COMPLEX_DTYPE[level]


def machine_eps(level: str) -> float:
    return MACHINE_EPS[level]


def min_level(a: str, b: str) -> str:
    """Lowest of two precision levels (paper: memory ops between phases run
    at the lowest precision of the adjacent compute phases)."""
    return a if _LEVELS.index(a) <= _LEVELS.index(b) else b


def level_index(level: str) -> int:
    """Position on the h < s < d ladder (0, 1, 2)."""
    return _LEVELS.index(level)


def max_level(levels: Sequence[str]) -> str:
    """Highest of a set of precision levels."""
    return max(levels, key=_LEVELS.index)


@dataclasses.dataclass(frozen=True)
class TileMap:
    """Static per-tile precision levels for the Phase-3 GEMM (tile-centric
    mixed precision, DESIGN.md §8).

    ``levels`` is a small ``(R_tiles, C_tiles)`` grid of ladder levels: the
    row axis evenly partitions the frequency-bin (batch) axis of ``F_hat``,
    the column axis its long model axis ``N_m``.  A cell says at which
    *storage* level the kernels may quantize that tile of the operand
    before contracting — accumulation always stays in the carrier dtype
    (the gemv phase's), so the effective level of a cell is
    ``min(cell, gemv)`` and a map can only ever *drop* precision.

    Frozen + tuple-backed: hashable, so tile-mapped configs remain valid
    jit static arguments and cache-key components.
    """

    levels: tuple

    def __post_init__(self):
        rows = tuple(tuple(r) for r in self.levels)
        if not rows or not rows[0]:
            raise ValueError("tile map must be non-empty")
        width = len(rows[0])
        for r in rows:
            if len(r) != width:
                raise ValueError("ragged tile map")
            for lvl in r:
                if lvl not in _LEVELS:
                    raise ValueError(f"bad tile precision level {lvl!r}")
        object.__setattr__(self, "levels", rows)

    @property
    def shape(self) -> tuple[int, int]:
        return (len(self.levels), len(self.levels[0]))

    # -- string codec (the cache-key ``;tiles=`` detail) --------------------
    def to_string(self) -> str:
        return "|".join("".join(r) for r in self.levels)

    @classmethod
    def from_string(cls, s: str) -> "TileMap":
        return cls(tuple(tuple(row) for row in s.split("|")))

    @classmethod
    def uniform(cls, level: str, shape: tuple[int, int] = (1, 1)) -> "TileMap":
        return cls(tuple((level,) * shape[1] for _ in range(shape[0])))

    def is_uniform(self) -> bool:
        flat = {lvl for row in self.levels for lvl in row}
        return len(flat) == 1

    def min_level(self) -> str:
        return min((l for row in self.levels for l in row), key=_LEVELS.index)

    def effective(self, gemv_level: str) -> tuple:
        """Per-cell effective storage levels: ``min(cell, gemv)``."""
        return tuple(tuple(min_level(l, gemv_level) for l in row)
                     for row in self.levels)


def tile_le(a: TileMap, b: TileMap) -> bool:
    """Pointwise domination: ``a <= b`` iff every cell of ``a`` is at a
    level no higher than ``b``'s (same shape required)."""
    if a.shape != b.shape:
        return False
    return all(_LEVELS.index(la) <= _LEVELS.index(lb)
               for ra, rb in zip(a.levels, b.levels)
               for la, lb in zip(ra, rb))


@dataclasses.dataclass(frozen=True)
class PrecisionConfig:
    """Precision level of each of the five FFTMatvec phases.

    Phase order matches the paper: (1) broadcast+pad, (2) FFT, (3) SBGEMV,
    (4) IFFT, (5) unpad+reduce.  ``tiles`` optionally refines the gemv
    phase below phase granularity: a :class:`TileMap` quantizing individual
    Phase-3 operand tiles (carrier accumulation unchanged) — ``None`` is
    the phase-uniform config, exactly the paper's lattice.
    """

    pad: str = "d"
    fft: str = "d"
    gemv: str = "d"
    ifft: str = "d"
    reduce: str = "d"
    tiles: Optional[TileMap] = None

    def __post_init__(self):
        for p in PHASES:
            lvl = getattr(self, p)
            if lvl not in _LEVELS:
                raise ValueError(f"bad precision level {lvl!r} for phase {p!r}")
        if self.tiles is not None and not isinstance(self.tiles, TileMap):
            object.__setattr__(self, "tiles", TileMap(self.tiles))

    # -- paper-style string codec ------------------------------------------
    @classmethod
    def from_string(cls, s: str) -> "PrecisionConfig":
        base, sep, tail = s.partition(";tiles=")
        if len(base) != 5:
            raise ValueError(f"precision string must have 5 chars, got {s!r}")
        tiles = TileMap.from_string(tail) if sep else None
        return cls(*base, tiles=tiles)

    def to_string(self) -> str:
        s = "".join(getattr(self, p) for p in PHASES)
        if self.tiles is not None:
            s += f";tiles={self.tiles.to_string()}"
        return s

    def levels(self) -> tuple[str, ...]:
        return tuple(getattr(self, p) for p in PHASES)

    # -- derived dtypes -----------------------------------------------------
    def phase_dtype(self, phase: str):
        return real_dtype(getattr(self, phase))

    def reorder_level(self, before: str, after: str) -> str:
        """Precision of the memory-only reorder between two compute phases."""
        return min_level(getattr(self, before), getattr(self, after))

    def highest(self) -> str:
        idx = max(_LEVELS.index(getattr(self, p)) for p in PHASES)
        return _LEVELS[idx]

    def replace(self, **kw) -> "PrecisionConfig":
        return dataclasses.replace(self, **kw)

    def gemv_tile_levels(self) -> Optional[tuple]:
        """Effective per-tile gemv storage levels (``min(cell, gemv)``),
        or None for a phase-uniform config."""
        if self.tiles is None:
            return None
        return self.tiles.effective(self.gemv)

    def cost_rank(self) -> float:
        """Sum of per-phase ladder indices — a model-level cost proxy that
        is strictly monotone under raising any phase's precision.  A tile
        map replaces the gemv index by the *mean* effective tile index, so
        mixed-tile configs rank strictly cheaper than their uniform base."""
        rank = sum(_LEVELS.index(getattr(self, p)) for p in PHASES)
        eff = self.gemv_tile_levels()
        if eff is not None:
            flat = [_LEVELS.index(l) for row in eff for l in row]
            rank += sum(flat) / len(flat) - _LEVELS.index(self.gemv)
        return rank


def _gemv_cells_le(a: PrecisionConfig, b: PrecisionConfig) -> bool:
    """gemv-phase comparison cell-wise (tile maps refine the phase level)."""
    ea, eb = a.gemv_tile_levels(), b.gemv_tile_levels()
    if ea is None and eb is None:
        return _LEVELS.index(a.gemv) <= _LEVELS.index(b.gemv)
    if ea is None:
        return all(_LEVELS.index(a.gemv) <= _LEVELS.index(l)
                   for row in eb for l in row)
    if eb is None:
        return all(_LEVELS.index(l) <= _LEVELS.index(b.gemv)
                   for row in ea for l in row)
    if a.tiles.shape != b.tiles.shape:
        return False              # different grids: incomparable
    return all(_LEVELS.index(la) <= _LEVELS.index(lb)
               for ra, rb in zip(ea, eb) for la, lb in zip(ra, rb))


def config_le(a: PrecisionConfig, b: PrecisionConfig) -> bool:
    """Lattice partial order: ``a <= b`` iff every phase of ``a`` runs at a
    level no higher than ``b``'s.  Under the eq.-(6) error model ``a`` is
    then no more accurate than ``b``, and under any cost model that is
    monotone in per-phase precision ``a`` is no more expensive.  Tile maps
    refine the gemv comparison cell-wise (same-shape maps compare
    pointwise; different grids are incomparable)."""
    if not all(_LEVELS.index(getattr(a, p)) <= _LEVELS.index(getattr(b, p))
               for p in PHASES if p != "gemv"):
        return False
    return _gemv_cells_le(a, b)


def config_lt(a: PrecisionConfig, b: PrecisionConfig) -> bool:
    """Strict lattice order: ``a <= b`` and ``a != b``."""
    return a != b and config_le(a, b)


def all_configs(levels: Sequence[str] = ("d", "s")) -> Iterator[PrecisionConfig]:
    """Enumerate every per-phase configuration over the given levels.

    ``levels=("d","s")`` reproduces the paper's 32 configurations;
    ``levels=("s","h")`` is the TPU-native 32; all three levels -> 243.
    """
    for combo in itertools.product(levels, repeat=len(PHASES)):
        yield PrecisionConfig(*combo)


DOUBLE = PrecisionConfig.from_string("ddddd")
SINGLE = PrecisionConfig.from_string("sssss")
TPU_BASELINE = SINGLE                       # f32 everywhere (TPU-native high)
TPU_FAST = PrecisionConfig.from_string("hhhhh")
# The paper's Pareto-optimal configs (Fig. 3): F matvec computes FFT+SBGEMV in
# low precision; F* matvec computes SBGEMV+IFFT in low precision.
PAPER_OPT_F = PrecisionConfig.from_string("dssdd")
PAPER_OPT_FSTAR = PrecisionConfig.from_string("ddssd")
# >=512 GPUs on Frontier: also reduce in low precision (paper §C.1: "dssds").
PAPER_OPT_F_LARGE = PrecisionConfig.from_string("dssds")
TPU_OPT_F = PrecisionConfig.from_string("shhss")
