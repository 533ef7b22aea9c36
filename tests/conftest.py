import os
import random
import sys
import tempfile
import zlib
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

# test runs keep JAX's persistent compile cache outside the checkout
# (set before any test module imports jax, which reads it at import)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(tempfile.gettempdir(),
                                   "dlaas-test-jax-cache"))

# NOTE: no XLA_FLAGS here — smoke tests and benches must see the real
# single device; multi-device tests force host devices in subprocesses
# of their own (tests/util_subproc.py).


@pytest.fixture(autouse=True)
def _registry_restored():
    """Each test starts from the published registry: a cut configuration
    the benchmark registers (under its configuration's name, which may be
    the published id) stays inside the test that registered it."""
    from repro.configs.registry import REGISTRY
    saved = dict(REGISTRY)
    yield
    REGISTRY.clear()
    REGISTRY.update(saved)


@pytest.fixture(autouse=True)
def _deterministic_seed(request):
    """Seed the global RNGs per test so runs are reproducible regardless
    of test ordering or -k selection.  Each test gets its own stable
    seed (derived from its node id) so reordering one test does not
    shift the random stream of every test after it."""
    seed = zlib.crc32(request.node.nodeid.encode()) & 0x7FFFFFFF
    random.seed(seed)
    try:
        import numpy as np
        np.random.seed(seed)
    except ImportError:  # pragma: no cover
        pass
