# NOTE: do NOT set --xla_force_host_platform_device_count here. Smoke tests
# and benches must see 1 device; only launch/dryrun.py forces 512 (and the
# multi-device tests spawn subprocesses with their own XLA_FLAGS).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _empty_program_cache():
    """Each test builds its own programs: the engine's process-wide program
    cache would otherwise hand a test the executables an earlier test in
    the same worker built, and the compile spans a test expects would not
    appear."""
    from repro.core import engine

    engine.clear_programs()
    yield
