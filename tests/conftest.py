import jax
import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — tests and benches must see the real
# (single-CPU) device set; only launch/dryrun.py forces 512 host devices.

# Hermetic containers have no `hypothesis`; fall back to the deterministic
# stub so all property-test modules collect and run (see _compat docstring).
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    from repro._compat import hypothesis_stub

    hypothesis_stub.install()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test module. On the CPU a
    program holds over a thousand memory mappings (an interpret-mode
    kernel inside `BiosignalStream.process`'s per-upload loop, compiled
    once per upload length), and a test worker that keeps every module's
    programs passes the kernel's limit (`vm.max_map_count`, 65530) and
    dies inside the compiler."""
    yield
    jax.clear_caches()
