"""The benchmark's own tests run on the host CPU at tiny sizes:

    python -m pytest bench/tests

They are not part of the repository's tier-1 suite (``pyproject.toml``
collects ``tests/`` only)."""
import os
import pathlib
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# a compile cache of the tests' own, away from the checkout's
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", tempfile.mkdtemp(prefix="bench-tests-jax-cache-")
)
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

