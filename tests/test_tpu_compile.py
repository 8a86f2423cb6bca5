"""Compile the main path's kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, so the chip's compiler (Mosaic for the Pallas
kernels) refuses here what it would refuse on the chip: block shapes not
aligned to the (8, 128) tiling, too much VMEM, a program that does not
fit.  Interpret mode checks none of that.  Shapes are the paper's
single-chip ones (``PAPER_SINGLE``: N_t=1000, N_d=100, N_m=5000; B =
N_t + 1 frequency bins), and the (104, 5120) tile-padded variant.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep these tests in this one file (a second file could go to
another worker, whose fixture would then skip).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.configs.fftmatvec_paper import PAPER_SINGLE
from repro.core import ExecOpts, FFTMatvec, PrecisionConfig
from repro.kernels import pad_cast, sbgemv

N_T, N_D, N_M = PAPER_SINGLE.N_t, PAPER_SINGLE.N_d, PAPER_SINGLE.N_m
B = N_T + 1
PLANE_SHAPES = [(B, N_D, N_M), (B, 104, 5120)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    """A described argument in the row-major layout a device array has."""
    layout = Layout(major_to_minor=tuple(range(len(shape))))
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=Format(layout, sharding))


def _compile(fn, *args):
    # x64 stays off, as on the chip (the test session turns it on): Mosaic
    # takes no 64-bit index maps, and the TPU ladder is h/s
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("shape", PLANE_SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("mode", ["N", "H"])
def test_sbgemv_compiles(one_chip, shape, dtype, mode):
    b, m, n = shape
    A = _arg(shape, dtype, one_chip)
    x = _arg((b, n if mode == "N" else m), dtype, one_chip)
    if mode == "N":
        fn = lambda Ar, Ai, xr, xi: sbgemv.sbgemv_n_complex(Ar, Ai, xr, xi)
    else:
        fn = lambda Ar, Ai, xr, xi: sbgemv.sbgemv_th_complex(
            Ar, Ai, xr, xi, conj=True)
    assert _has_kernel(_compile(fn, A, A, x, x))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("mode", ["N", "H"])
@pytest.mark.parametrize("tiled", [False, True], ids=["plain", "tiled"])
def test_sbgemm_compiles(one_chip, dtype, mode, tiled):
    S = 128
    A = _arg((B, N_D, N_M), dtype, one_chip)
    X = _arg((B, N_M if mode == "N" else N_D, S), dtype, one_chip)
    lvl = _arg((B, -(-N_M // 512)), jnp.int32, one_chip)   # block_n=512
    if mode == "N":
        plain = lambda Ar, Ai, Xr, Xi, lv: sbgemv.sbgemm_n_complex(
            Ar, Ai, Xr, Xi)
        tile = lambda Ar, Ai, Xr, Xi, lv: sbgemv.sbgemm_n_complex_tiled(
            Ar, Ai, Xr, Xi, lv)
    else:
        plain = lambda Ar, Ai, Xr, Xi, lv: sbgemv.sbgemm_th_complex(
            Ar, Ai, Xr, Xi, conj=True)
        tile = lambda Ar, Ai, Xr, Xi, lv: sbgemv.sbgemm_th_complex_tiled(
            Ar, Ai, Xr, Xi, lv, conj=True)
    assert _has_kernel(_compile(tile if tiled else plain, A, A, X, X, lvl))


@pytest.mark.parametrize("mode", ["N", "T"])
def test_sbgemm_real_tiled_compiles(one_chip, mode):
    S = 128
    A = _arg((B, N_D, N_M), jnp.float32, one_chip)
    X = _arg((B, N_M if mode == "N" else N_D, S), jnp.float32, one_chip)
    lvl = _arg((B, -(-N_M // 512)), jnp.int32, one_chip)
    fn = sbgemv.sbgemm_n_real_tiled if mode == "N" \
        else sbgemv.sbgemm_th_real_tiled
    assert _has_kernel(_compile(fn, A, X, lvl))


@pytest.mark.parametrize("tiled", [False, True], ids=["plain", "tiled"])
def test_sbgemm_gram_compiles(one_chip, tiled):
    """The data-space Gram F F^H per bin, as the ops layer runs it: the
    kernel on conjugate-transposed (B, N_m, N_d) planes, (B, N_d, N_d)
    out (the parameter-space G would be 100 GB at this shape)."""
    A = _arg((B, N_M, N_D), jnp.float32, one_chip)
    lvl = _arg((B, 1), jnp.int32, one_chip)         # block_n=128 > N_d
    if tiled:
        fn = lambda Ar, Ai, lv: sbgemv.sbgemm_gram_tiled(Ar, Ai, lv,
                                                         block_n=128)
    else:
        fn = lambda Ar, Ai, lv: sbgemv.sbgemm_gram_complex(Ar, Ai,
                                                           block_n=128)
    assert _has_kernel(_compile(fn, A, A, lvl))


@pytest.mark.parametrize("direction", ["pad", "unpad"])
def test_pad_cast_compiles(one_chip, direction):
    rows = N_D * 8
    if direction == "pad":
        fn = lambda x: pad_cast.pad_cast(x, 2 * N_T, jnp.bfloat16)
        x = _arg((rows, N_T), jnp.float32, one_chip)
    else:
        fn = lambda x: pad_cast.unpad_cast(x, N_T, jnp.bfloat16)
        x = _arg((rows, 2 * N_T), jnp.float32, one_chip)
    assert _has_kernel(_compile(fn, x))


def test_paper_matvec_compiles_with_pallas_phase3(one_chip):
    """One whole ``sssss`` matvec at the paper shape: Phase 3 is a Pallas
    kernel, and the program reads the stored F_hat planes in place (its
    scratch stays far below one plane: no relayout copy of F_hat)."""
    cfg = PrecisionConfig.from_string("sssss")
    opts = ExecOpts(backend="tpu-pallas")
    F = _arg((B, N_D, N_M), jnp.float32, one_chip)
    x = _arg((N_M, N_T), jnp.float32, one_chip)
    fn = lambda Fr, Fi, x: FFTMatvec(Fr, Fi, N_T, cfg, opts).matvec(x)
    compiled = _compile(fn, F, F, x)
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"[{B},{N_D},{N_M}]" in line]
    assert kernels, "Phase 3 did not compile to a Pallas kernel"
    plane_bytes = B * N_D * N_M * 4
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes // 8
