import os

# Give the suite a few virtual CPU devices so the dist layer (pipeline
# stages, mesh construction, compressed collectives) is exercised for real.
# Must be set before the first jax import anywhere in the test session.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 " + _flags).strip()

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--chaos-seeds", default="0..1", metavar="SPEC",
        help="fault-injection seeds for the chaos matrix "
             "(tests/test_faults.py): 'a..b' inclusive range or a comma "
             "list, e.g. '0..4' or '3,7,11'")


def pytest_generate_tests(metafunc):
    if "chaos_seed" in metafunc.fixturenames:
        spec = metafunc.config.getoption("--chaos-seeds")
        if ".." in spec:
            lo, hi = spec.split("..", 1)
            seeds = list(range(int(lo), int(hi) + 1))
        else:
            seeds = [int(s) for s in spec.split(",") if s.strip()]
        metafunc.parametrize("chaos_seed", seeds)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_shell_scene(rng, resolution=24, channels=4):
    """Sphere-shell occupancy (surface-sparse, like real scans)."""
    r = resolution
    xx, yy, zz = np.meshgrid(*[np.arange(r)] * 3, indexing="ij")
    d = np.sqrt((xx - r / 2) ** 2 + (yy - r / 2) ** 2 + (zz - r / 2) ** 2)
    occ = np.abs(d - r / 3) < 0.9
    dense = np.zeros((r, r, r, channels), np.float32)
    dense[occ] = rng.normal(size=(occ.sum(), channels)).astype(np.float32)
    return dense


@pytest.fixture(scope="module")
def shell():
    """A 28^3 sphere shell: its tensor, 3x3x3 neighbour table and
    submanifold CIRF indices."""
    import jax.numpy as jnp

    from repro.core.hashgrid import build_neighbor_table, kernel_offsets
    from repro.core.sparse_conv import submanifold_coir
    from repro.sparse.tensor import from_dense

    rng = np.random.default_rng(7)
    dense = make_shell_scene(rng, 28, 4)
    t = from_dense(dense)
    nbr = np.asarray(build_neighbor_table(
        t.coords, t.mask, jnp.asarray(kernel_offsets(3)), 28))
    coir = submanifold_coir(t, 28, 3)
    return t, nbr, np.asarray(coir.indices)
