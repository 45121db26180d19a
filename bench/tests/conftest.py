"""Tests of the benchmark itself, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)

# keep CPU-compiled programs out of the checkout's cache directory
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))
