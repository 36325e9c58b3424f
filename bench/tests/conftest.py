import os
import sys

# The benchmark's tests run on the CPU, with the Pallas kernels interpreted.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
for p in (os.path.join(_ROOT, "src"), _ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    """run_cell turns the persistent compile cache on for its process;
    give the settings back so later tests in a worker see the defaults."""
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    old = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in old.items():
        jax.config.update(n, v)
