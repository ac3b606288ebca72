"""Test harness: run everything on a virtual 8-device CPU mesh.

SURVEY.md §4: the reference has no tests; the strategy here is built from
scratch — tiny random-weight configs, golden parity against HF transformers,
and multi-device sharding tests on `--xla_force_host_platform_device_count=8`
CPU devices (no pod required).
"""

import os

# The test suite always runs on a virtual 8-device CPU mesh; the chip is
# exercised by chip_smoke.py through the chip tool. The XLA_FLAGS env must
# be set before the CPU backend initializes. The platform is forced via
# jax.config as well as by the tier-1 command's JAX_PLATFORMS=cpu, so a
# bare `pytest` on a machine with an accelerator still runs here.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

jax.config.update("jax_threefry_partitionable", True)

# The persistent compile cache stays off inside the pytest process: entry
# points under test call utils/compile_cache.configure(), and a suite
# whose compile-count pins (prof.compiles, jit cache sizes) depended on
# what an earlier run left on disk would not be a test. The AOT compiles
# for a described chip (test_chip_compile.py) could not read their
# entries back anyway.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`) and the smoke targets",
    )


# The suites whose execution exercises the engine-thread boundary run
# with the CK-THREAD runtime twin armed (runtime/threadcheck): the
# scheduler stamps its engine thread and every annotated engine/pool
# mutator asserts domain membership — so the static thread-domain model
# (cake_tpu/analysis/thread_domains.py) is validated against real
# execution, not just the AST.
_THREAD_STRICT_SUITES = ("test_serve", "test_kvpool", "test_disagg",
                         "test_gateway", "test_sp_serving")


@pytest.fixture(autouse=True)
def _thread_strict_twin(request):
    if request.module.__name__.rpartition(".")[2] in _THREAD_STRICT_SUITES:
        from cake_tpu.runtime import threadcheck

        prev = threadcheck.set_strict(True)
        yield
        threadcheck.set_strict(prev)
    else:
        yield


@pytest.fixture(scope="session")
def tiny_config():
    from cake_tpu.models.config import tiny

    return tiny()


@pytest.fixture(scope="session")
def tiny_params(tiny_config):
    from cake_tpu.models.llama import init_params

    return init_params(tiny_config, jax.random.PRNGKey(0))
