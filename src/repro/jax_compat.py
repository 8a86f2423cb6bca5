"""Thin fronts over the JAX mesh APIs, written once for the installed JAX.

``shard_map`` turns off the varying-manual-axes check: the plans place
their collectives explicitly, and the check cannot follow replication
through the FFTs and mixed-dtype casts between them.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    is changed here.  Otherwise the cache lives at a fixed path, the
    ``.jax_cache/`` directory of the checkout: the path is part of what
    the cache is found by, so it never depends on a temp dir, a pid or
    the time.  Call it before the first compile (the chip smoke,
    ``benchmarks/run.py`` and the examples do).
    """
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def forced_host_devices_env(n: int, base_env=None) -> dict:
    """Environment for a subprocess that must see ``n`` forced host
    devices, with this repo's ``src`` importable.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``): its devices
    are simulated host devices, and on a machine with an accelerator the
    parent already holds the chip, so a child reaching for it would fail
    or hang.  ``XLA_FLAGS`` and ``PYTHONPATH`` are *extended*, never
    clobbered: the device-count flag is appended to any inherited
    ``XLA_FLAGS`` (appended last so it wins on conflict) and ``src`` is
    prepended to any inherited ``PYTHONPATH``.  The shared helper for
    ``tests/test_distributed.py``, the fig4 bench and
    ``calibrate_overlap``.
    """
    import os
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={n}"
    inherited = env.get("XLA_FLAGS")
    env["XLA_FLAGS"] = f"{inherited} {flag}" if inherited else flag
    src = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prior = env.get("PYTHONPATH")
    env["PYTHONPATH"] = f"{src}{os.pathsep}{prior}" if prior else src
    return env
