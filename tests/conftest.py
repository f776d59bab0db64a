"""Test harness configuration.

The analog of the reference's ``tests/conftest.py`` + ``tests/unit/common.py``
device gating: unit tests run on a **virtual 8-device CPU mesh**
(``--xla_force_host_platform_device_count=8``) so the full suite runs without
TPUs — the same motivation as the reference's CPU CI lanes. The platform is
forced to cpu *before* any backend is initialized, so a test run never
claims a chip even on a machine that has one.
"""

import os

# Must happen before the first JAX backend initialization.
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + _flag
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Files that one worker holds for minutes, by their seconds in one process
# (ROADMAP D8; `--durations`). Under ``--dist loadfile`` xdist hands files
# out by their NUMBER of tests, most first, so a file of one long test runs
# last and five workers idle behind it (~50-100 s of a Tier-1 run that
# stands two minutes from its clock). The order below is xdist's own, by
# number of tests, with these files ahead of it by their cost; the tests a
# worker runs, and their order inside a file, are what they were.
_LONG_FILES = {
    "tests/unit/ops/test_paged_attention.py": 350,
    "tests/unit/accelerator/test_chip_path.py": 200,
    "tests/unit/launcher/test_elastic_e2e.py": 119,
    "tests/model/test_realtext_convergence.py": 110,
}


def pytest_configure(config):
    # (xdist's reordering by count is replaced by the one below)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    by_file = {}
    for item in items:
        by_file.setdefault(item.nodeid.split("::", 1)[0], []).append(item)
    order = sorted(by_file, key=lambda name: (
        -_LONG_FILES.get(name, 0), -len(by_file[name])))
    items[:] = [item for name in order for item in by_file[name]]


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Each test gets a fresh global mesh registry."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    yield
    mesh_mod.reset_mesh()


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_compile_cache():
    """Clear JAX's jit/executable caches at module boundaries: a single
    process that accumulates ~400+ XLA:CPU compiled programs segfaults
    inside backend_compile_and_load (native compiler state — observed
    reproducibly at tests/unit/runtime/zero in monolithic runs while
    every chunked run passes). Cost: library-level jitted functions
    shared across test modules recompile after each boundary — accepted
    as the price of bounding native compiler state."""
    yield
    jax.clear_caches()


@pytest.fixture
def eight_device_mesh():
    from deepspeed_tpu.parallel import initialize_mesh

    return initialize_mesh()


@pytest.fixture
def tp_mesh():
    """Factory fixture for a ``(data, model)`` global mesh on the forced
    multi-device CPU host: ``mesh = tp_mesh(data=4, model=2)`` builds the
    mesh AND installs it as the process-global mesh (torn down by the
    autouse ``_reset_global_mesh``).

    This only works because of two environment settings made at the TOP
    of this conftest, before JAX initializes a backend — repeat them in
    any subprocess (CLI tools, multi-process tests) BEFORE its local
    ``import jax``:

    * ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` splits the
      host CPU into 8 virtual XLA devices. It is read once at backend
      initialization; exporting it after ``jax.devices()`` has run is a
      silent no-op and every mesh axis comes up size 1.
    * ``JAX_PLATFORMS=cpu`` must ride along: the forced host devices
      exist only on the ``cpu`` platform, so on a machine with an
      accelerator (where JAX defaults to it) the flag above would
      otherwise do nothing — the combination is what pins the 8-device
      topology tests rely on.
    """
    from deepspeed_tpu.parallel import mesh as mesh_mod

    def _make(data: int = 8, model: int = 1):
        mesh = mesh_mod.initialize_mesh(data=data, model=model)
        mesh_mod.set_mesh(mesh)
        return mesh

    return _make
