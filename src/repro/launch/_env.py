"""Environment hooks that must run before jax initializes a backend or
compiles a program.

Importing this module (like anything under ``repro``) imports jax, which is
safe: XLA reads XLA_FLAGS when the *backend* initializes — at the first
device query — not at import time, and the compilation cache is consulted at
the first compile. Callers just have to apply the hooks before building a
mesh or touching devices; the stream CLIs run them at module import, ahead of
everything else.
"""
from __future__ import annotations

import os
import pathlib
import re

import jax

# the persistent compilation cache's home when the environment names none:
# fixed and inside the checkout (git-ignored), because the directory is part
# of what a later run must find again
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
    nothing is set here. Otherwise the cache goes to ``COMPILE_CACHE_DIR``.
    A cache hit skips the backend compile, so it also skips the
    ``backend_compile`` event that ``repro.engine.XlaCompileCounter``
    counts.

    The cache is keyed on the programs' op metadata too: the named scopes
    (``jax.named_scope``) live there, and the profiler reports an op under
    the scope path of the executable that ran. Keyed without it, a program
    that differs from a cached one only in its scopes would run, and be
    profiled, under the cached program's names. Source paths in the
    metadata are taken relative to the checkout, so a copy of the same
    code elsewhere finds the same entries."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(str(COMPILE_CACHE_DIR.parent)) + "/",
    )
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def apply_host_devices(argv) -> None:
    """Honor ``--host-devices N`` / ``--host-devices=N``: force N CPU host
    devices via XLA_FLAGS so device meshes are testable without accelerators
    (docs/scaling.md, "Driving it")."""
    n = None
    for i, arg in enumerate(argv):
        if arg == "--host-devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif arg.startswith("--host-devices="):
            n = arg.split("=", 1)[1]
    if n is None or int(n) <= 0:
        return  # 0 is the CLIs' documented "off" default
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(n)}"
    )
