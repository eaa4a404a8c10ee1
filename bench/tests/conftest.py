"""CPU tests of the benchmark: ``python -m pytest bench/tests``."""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compiled CPU programs stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-"))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
