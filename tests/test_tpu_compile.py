"""Compile the main path's kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, so the chip's compiler (Mosaic for the Pallas
kernels) refuses here what it would refuse on the chip: block shapes not
aligned to the (8, 128) tiling, too much VMEM, a program that does not
fit.  Interpret mode checks none of that.  Shapes are the paper's
single-chip ones (``PAPER_SINGLE``: N_t=1000, N_d=100, N_m=5000; B =
N_t + 1 frequency bins), and the (104, 5120) tile-padded variant.  A
whole program takes the F_hat planes in the format the program's own
set-up stores them in on the described chip (``stored_planes``), never a
layout written here: planes in another layout compile to a relayout copy
of 4 GB in every program that reads them.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep these tests in this one file (a second file could go to
another worker, whose fixture would then skip).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.backend import resolve_backend
from repro.configs.fftmatvec_paper import PAPER_SINGLE
from repro.core import ExecOpts, FFTMatvec, PrecisionConfig, fftmatvec
from repro.kernels import pad_cast, sbgemv

N_T, N_D, N_M = PAPER_SINGLE.N_t, PAPER_SINGLE.N_d, PAPER_SINGLE.N_m
B = N_T + 1
PLANE_SHAPES = [(B, N_D, N_M), (B, 104, 5120)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    """A described argument in row-major layout: a kernel's operand, or a
    program's vector input."""
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


def _plane_copies(compiled, shape) -> list:
    """The program's ``copy`` instructions of an operand of ``shape``."""
    dims = "[" + ",".join(map(str, shape)) + "]"
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if re.search(r"\bcopy\(", line) and dims in line]


@pytest.fixture(scope="module")
def stored_planes(one_chip):
    """The F_hat planes at the paper shape as the program stores them on
    the described chip, by rung: the output formats of the compiled
    ``tpu-pallas`` set-up (``sssss``) and of ``with_precision``'s cast
    from there (``shhss``); no layout is written here."""
    spec = resolve_backend("tpu-pallas")
    cfg = PrecisionConfig.from_string("sssss")
    F_col = _arg((N_T, N_D, N_M), jnp.float32, one_chip)
    with jax.enable_x64(False):
        setup = jax.jit(fftmatvec._setup(spec, cfg)).lower(F_col)
        f32 = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=f)
               for s, f in zip(setup.out_info,
                               setup.compile().output_formats)]
        cast = jax.jit(lambda p: p.astype(jnp.bfloat16)).lower(
            f32[0]).compile()
    bf16 = jax.ShapeDtypeStruct(f32[0].shape, jnp.bfloat16,
                                sharding=cast.output_formats)
    return {"sssss": f32, "shhss": [bf16, bf16]}


@pytest.fixture(scope="module")
def stored_programs(one_chip, stored_planes):
    """``(cfg, call) -> compiled``: each program that reads the operator at
    the paper shape, compiled once, with the planes as the program stores
    them (tile-padded, in the device's default layout for that shape)."""
    compiled = {}

    def get(cfg, call):
        if (cfg, call) not in compiled:
            prec_cfg = PrecisionConfig.from_string(cfg)
            opts = ExecOpts(backend="tpu-pallas")
            x = _arg((N_D if call == "rmatmat" else N_M, N_T), jnp.float32,
                     one_chip)

            def fn(Fr, Fi, x):
                op = FFTMatvec(Fr, Fi, N_T, prec_cfg, opts,
                               dims=(N_D, N_M))
                if call == "gram":
                    return op.gram(space="parameter", mode="exact").apply(x)
                return getattr(op, call)(x)

            compiled[cfg, call] = _compile(fn, *stored_planes[cfg], x)
        return compiled[cfg, call]

    return get


def test_paper_matvec_compiles_with_pallas_phase3(stored_programs):
    """One whole ``sssss`` matvec at the paper shape: Phase 3 is a Pallas
    kernel, and the program reads the stored F_hat planes in place (its
    scratch stays far below one plane: no relayout copy of F_hat)."""
    compiled = stored_programs("sssss", "matvec")
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"[{B},{N_D},{N_M}]" in line]
    assert kernels, "Phase 3 did not compile to a Pallas kernel"
    plane_bytes = B * N_D * N_M * 4
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes // 8


@pytest.mark.parametrize("cfg,call", [("sssss", "matvec"),
                                      ("sssss", "rmatmat"),
                                      ("sssss", "gram"),
                                      ("shhss", "matvec")])
def test_programs_read_stored_planes_without_copies(stored_programs, cfg,
                                                    call):
    """Each program that reads the operator takes the planes as set-up
    stored them and hands the Phase-3 kernel a view of them: no copy of a
    plane, and scratch far below one plane."""
    compiled = stored_programs(cfg, call)
    assert not _plane_copies(compiled, (B, N_D, N_M))
    assert not _plane_copies(compiled, (B, 104, 5120))
    plane_bytes = B * N_D * N_M * 4
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes // 8


def _entry(text) -> dict:
    """{name: line} of each instruction of the compiled entry computation."""
    body = text[text.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    lines = {}
    for line in body.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ", line)
        if m:
            lines[m.group(1)] = line
    return lines


def _called(text, line) -> str:
    """The body of the computation a fusion calls ('' for other ops)."""
    m = re.search(r"calls=%?([\w.\-]+)", line)
    if not m:
        return ""
    start = text.index(f"\n%{m.group(1)} ")
    return text[start:text.index("\n}", start)]


def _phase3_to_ifft(text) -> list:
    """The entry instructions that carry Phase 3's output into the IFFT:
    from the SBGEMV kernel through every user, up to and including the
    first matmul fusions of the IFFT (a length-2000 FFT is a DFT matmul
    on the v5e)."""
    entry = _entry(text)
    reached = [n for n, line in entry.items() if n.startswith("sbgemv_")
               and 'custom_call_target="tpu_custom_call"' in line]
    assert reached, "Phase 3 did not compile to a Pallas kernel"
    frontier = list(reached)
    while frontier:
        name = frontier.pop()
        if "kind=kOutput" in entry[name]:
            continue                    # the IFFT's matmul: stop here
        for user, line in entry.items():
            if user not in reached and re.search(
                    rf"%{re.escape(name)}[,)]", line.split(" = ", 1)[1]):
                reached.append(user)
                frontier.append(user)
    return [entry[n] for n in reached]


def _rounds_to_bf16(text, line) -> bool:
    """The instruction's value is stored as bf16, or it rounds to bf16's
    8 exponent and 7 mantissa bits in a form the compiler keeps."""
    rounding = re.compile(r"reduce-precision\([^)]*\), exponent_bits=8, "
                          r"mantissa_bits=7")
    result = line.split(" = ", 1)[1].split("(", 1)[0]
    return "bf16[" in result or bool(rounding.search(_called(text, line))
                                     or rounding.search(line))


def test_shhss_matvec_rounds_phase3_output_to_bf16(stored_programs):
    """``shhss`` states Phase 3's output at bf16 and the IFFT at f32.  On
    the way from the kernel to the IFFT the value is stored as bf16 or
    passes a ``reduce-precision`` to bf16's bits.  A cast down and back up
    in one fusion is not enough: allowed excess precision, the TPU
    compiler may drop that pair and carry the kernel's f32 on."""
    text = stored_programs("shhss", "matvec").as_text()
    path = _phase3_to_ifft(text)
    assert any("kind=kOutput" in line for line in path), \
        "no IFFT matmul follows Phase 3"
    assert any(_rounds_to_bf16(text, line) for line in path), \
        [line.strip()[:100] for line in path]


@pytest.mark.parametrize("call", ["matvec", "rmatmat", "gram"])
def test_sssss_programs_round_nowhere(stored_programs, call):
    """At ``sssss`` no stage boundary narrows, so no program gains a
    rounding op (its plane copies: the test above)."""
    assert "reduce-precision(" not in stored_programs("sssss", call).as_text()


def test_mesh_setup_stores_planes_the_kernel_reads(topo):
    """The mesh set-up (``from_block_column`` on a 1x4 grid) stores each
    device's planes tile-padded, in the default layout the Phase-3 kernel
    reads, so the mesh matvec copies none.  At this shape the v5e's
    default layout of an unpadded block is not row-major, and the kernel
    would copy it."""
    n_t, n_d, n_m = 100, 12, 4 * 250
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("row", "col"))
    cfg = PrecisionConfig.from_string("sssss")
    spec = resolve_backend("tpu-pallas")
    F_col = jax.ShapeDtypeStruct(
        (n_t, n_d, n_m), jnp.float32,
        sharding=NamedSharding(mesh, P(None, "row", "col")))
    with jax.enable_x64(False):
        setup = fftmatvec._setup(spec, cfg, mesh).lower(F_col)
        formats = setup.compile().output_formats
    assert all(f.layout.major_to_minor == (0, 1, 2) for f in formats)
    planes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=f)
              for s, f in zip(setup.out_info, formats)]
    assert planes[0].shape == (n_t + 1, 16, 4 * 256)
    x = jax.ShapeDtypeStruct((n_m, n_t), jnp.float32,
                             sharding=NamedSharding(mesh, P("col", None)))
    fn = lambda Fr, Fi, x: FFTMatvec(
        Fr, Fi, n_t, cfg, ExecOpts(backend="tpu-pallas"), mesh=mesh,
        dims=(n_d, n_m)).matvec(x)
    compiled = _compile(fn, *planes, x)
    assert _has_kernel(compiled)
    assert not _plane_copies(compiled, (n_t + 1, n_d, n_m // 4))
    assert not _plane_copies(compiled, (n_t + 1, 16, 256))


@pytest.mark.parametrize("call", ["matvec", "rmatmat", "gram"])
def test_kernels_and_fusions_carry_stage_scopes(one_chip, call):
    """Each Pallas kernel is named after its function, and every kernel and
    fusion of the compiled program (at a small shape) carries the plan
    executor's ``fftmatvec/<stage kind>`` scope in its ``op_name``, which a
    device profile shows for each op."""
    n_t, n_d, n_m = 64, 8, 512
    cfg = PrecisionConfig.from_string("sssss")
    opts = ExecOpts(backend="tpu-pallas")
    F = _arg((n_t + 1, n_d, n_m), jnp.float32, one_chip)
    x = _arg((n_d if call == "rmatmat" else n_m, n_t), jnp.float32, one_chip)

    def fn(Fr, Fi, x):
        op = FFTMatvec(Fr, Fi, n_t, cfg, opts)
        if call == "gram":
            return op.gram(space="parameter", mode="exact").apply(x)
        return getattr(op, call)(x)

    text = _compile(fn, F, F, x).as_text()
    entry = text[text.index("\nENTRY"):].splitlines()
    kernel = "sbgemv_n_complex" if call == "matvec" else "sbgemv_th_complex"
    assert any(line.lstrip().startswith(f"%{kernel}.")
               and f"/fftmatvec/gemv/{kernel}/" in line for line in entry)
    timed = [line for line in entry
             if 'custom_call_target="tpu_custom_call"' in line
             or re.search(r"\sfusion\(", line)]
    assert timed
    scoped = re.compile(r'op_name="[^"]*/fftmatvec/[a-z_]+/')
    # a fusion without metadata is judged by what it calls
    bare = [line.strip()[:100] for line in timed
            if not scoped.search(line)
            and not scoped.search(_called(text, line))]
    assert not bare
