"""Where the program runs and where its compiled code is kept.

Two decisions, each made in exactly one place:

- :func:`on_tpu` — compiled for the TPU, or the explicit CPU test mode.
  Every site that used to look at ``jax.default_backend()`` for itself
  (engine resolution, interpret-mode Pallas, buffer donation, the
  multi-process row alignment) calls it, so a machine whose TPU failed
  to initialise cannot train on the CPU and exit 0.
- :func:`compilation_cache_dir` — where JAX's persistent compilation
  cache lives. Entry points (``chip_smoke.py``, ``bench.py``,
  ``tests/conftest.py``) call it before the first compile.
"""
from __future__ import annotations

import os
import re

from . import log

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def on_tpu() -> bool:
    """True: the backend is a TPU, kernels compile for it. False: the
    process asked for the CPU by name (``JAX_PLATFORMS=cpu`` or
    ``jax.config.update("jax_platforms", "cpu")`` — the tests and
    ``bench.py --micro``), which selects the XLA engine by default and
    runs Pallas kernels in interpret mode when the fused engine is
    requested. Anything else — no accelerator found and JAX fell back
    by itself, a GPU — is fatal and names the platform JAX found."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True
    requested = str(jax.config.jax_platforms or "")
    if requested.split(",")[0].strip() == "cpu":
        return False
    log.fatal(
        "lightgbm_tpu runs compiled on a TPU, or on the CPU when the "
        "process asks for it by name (JAX_PLATFORMS=cpu: XLA engine, "
        "interpret-mode kernels, for tests). JAX found platform '%s' "
        "(device kind '%s') with jax_platforms=%r; refusing to train on "
        "a device nobody asked for", backend,
        jax.devices()[0].device_kind, requested or None)


def compilation_cache_dir(requested: str = "") -> str:
    """Place JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself, nothing is
    set in code, and a ``compilation_cache_dir`` config key (passed as
    ``requested``) yields to it with a log line. Unset: ``requested``,
    else ``<checkout>/.jax_cache`` — a fixed path derived from the
    package's own location, because a cache directory that moves between
    runs never hits. Must run before the process's first compile (JAX
    decides once whether the cache is in use).

    Either way the cache key covers the instructions' metadata: JAX
    strips it by default, and a cached executable then keeps the
    ``op_name``s of whichever program was compiled first — a profiler
    trace would attribute device time by ``lgbm.*`` phase scopes
    (docs/Observability.md section 3) that the running code no longer
    has, or lack the ones it has."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(_CACHE_ENV)
    if env:
        if requested and requested != env:
            log.info("compilation_cache_dir=%s yields to %s=%s",
                     requested, _CACHE_ENV, env)
        return env
    path = requested or _DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def scoped_vmem_bytes(compiled) -> int | None:
    """Most scoped VMEM a Pallas kernel of a compiled TPU executable asks
    for: its stack (values and spills), beside the pipeline's operand
    windows. Read from the ``tpu_custom_call``'s backend config in the
    compiled text; None where the text names none (no kernel, no TPU)."""
    found = re.findall(r'used_scoped_memory_configs":\[\{"memory_space":"1",'
                       r'"offset":"0","size":"(\d+)"', compiled.as_text())
    return max(map(int, found)) if found else None
