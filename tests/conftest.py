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
# The suite's seconds are compiles: ~30,000 programs, most of them run
# once, and three fifths of a test's seconds inside XLA:CPU's compile (a
# listener on jax's monitoring events over 1,050 cases: 14,957 compiles,
# 2,033 of 3,451 s; tracing 13 %, lowering 17 %; ROADMAP D8). This process
# compiles them without LLVM's optimisation passes and with the elemental
# emitters in place of the MLIR fusion pipeline: a one-op program in 16 ms
# where it took 33, a kernel in interpret mode a fifth sooner.
# Whole runs of this tree on one machine (six workers, PR 58): 1,127 s
# without the two flags, 820, 762 and 731 s with them (the parent 1,293 s).
# XLA reads XLA_FLAGS once, at the first compile below; the two flags then
# leave the environment, so what a test LAUNCHES (the launcher's workers,
# the rehearsals of ``perf/tools/``, chip_smoke.py) compiles as a user's
# process does and runs at full speed inside its timed windows.
_LIGHT_COMPILES = {"xla_backend_optimization_level": 0,
                   "xla_cpu_use_fusion_emitters": False}
_USERS_COMPILES = {"xla_backend_optimization_level": 3,     # XLA's defaults
                   "xla_cpu_use_fusion_emitters": True}
_LIGHT_FLAGS = " ".join(f"--{name}={str(value).lower()}"
                        for name, value in _LIGHT_COMPILES.items())
_inherited = os.environ["XLA_FLAGS"]
os.environ["XLA_FLAGS"] = _inherited + " " + _LIGHT_FLAGS

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.jit(lambda: 0)()
os.environ["XLA_FLAGS"] = _inherited

import pytest  # noqa: E402

# Files that one worker holds for minutes, by their seconds in a whole run
# of PR 58's tree (six workers; ROADMAP D8; the run's junit file). Under
# ``--dist loadfile`` xdist hands files out by their NUMBER of tests, most
# first, so a file of one long test runs last and five workers idle behind
# it. The order below is xdist's own, by number of tests, with these files
# ahead of it by their cost; the tests a worker runs, and their order inside
# a file, are what they were. Only these four: with every file over 150 s
# listed (fifteen, before the light compiles), the heaviest files all ran
# at once, each stretched by a quarter to a third (compile-heavy files use
# several cores each), and the run took 1,251 s where this order took 1,226
# (PR 58, two whole runs).
_LONG_FILES = {
    "tests/unit/ops/test_paged_attention.py": 298,
    "tests/unit/accelerator/test_chip_path.py": 188,
    "tests/model/test_realtext_convergence.py": 126,
    "tests/unit/launcher/test_elastic_e2e.py": 101,
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
def light_compiles():
    """``XLA_FLAGS`` for the workers of a test that launches processes of
    its own and times nothing in them: they compile as this one does."""
    return _LIGHT_FLAGS


@pytest.fixture
def users_compiles():
    """``compiler_options`` under which one program compiles as a user's
    process compiles it: for the few tests that hold the ROUNDINGS of two
    different programs to each other (a tolerance of 1e-6; a parameter whose
    gradient is zero but for rounding, through Adam), which the light
    compiles above do not keep."""
    return dict(_USERS_COMPILES)


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
