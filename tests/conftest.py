"""Test harness: run everything on a virtual 8-device CPU mesh.

SURVEY.md §4: the reference has no tests; the strategy here is built from
scratch — tiny random-weight configs, golden parity against HF transformers,
and multi-device sharding tests on `--xla_force_host_platform_device_count=8`
CPU devices (no pod required).

How tier-1 is run (the driver's command is `commands` in
`/root/TESTS_LAST_RUN.json`): `pytest tests/ -m 'not slow' -n 6 --dist
loadfile` under `timeout 1470`, on the CPU. Six workers; a FILE goes to
one worker whole, files with the most cases first; passes are counted
from the junit file (`--junitxml`), which also holds every case's
seconds: `ROADMAP.md` T1 has the table of the heaviest files and how to
make it anew. A run the clock cuts counts only as far as it got, so the
suite is kept well inside the clock, by two rules:

- A file whose cases sum to more than a fifth of the run's wall time is
  split along its own section headings into files of whole sections;
  what several parts need (a family's config, its parameters, an engine
  builder) moves into a plain helper module beside them (`*_kit.py`),
  imported by each part, not into this conftest.
- No wall-clock assertion on a CPU. A speed claim is asserted on what
  makes it so (a compiled program's FLOPs and bytes, a count of
  dispatches or of layer applications), never on `perf_counter`: a CPU
  timing is no device number, and it moves with the other five workers.

No module-scoped engines shared between cases: a case builds what it
needs, and the per-process compile cache below makes building it again
cheap.
"""

import atexit
import os
import shutil
import tempfile

# The test suite always runs on a virtual 8-device CPU mesh; the chip is
# exercised by chip_smoke.py through the chip tool. The XLA_FLAGS env must
# be set before the CPU backend initializes. The platform is forced via
# jax.config as well as by the tier-1 command's JAX_PLATFORMS=cpu, so a
# bare `pytest` on a machine with an accelerator still runs here.
#
# XLA's CPU backend is asked for less (optimization level 0, LLVM's
# expensive passes off): two thirds of a family file's time was that
# backend compiling tiny programs that then run for milliseconds, and the
# files measured lose a fifth to a quarter of their time with the two
# flags. A CPU program's bits change with them, which is sound because
# tier-1 compares programs with each other and with float32 references
# under ONE set of flags, and never with the chip: nothing here reads a
# speed or a bit pattern that the served program on a TPU would share.
# The AOT compiles for a described chip (test_chip_compile*.py) go through
# the TPU compiler and pass unchanged under them.
flags = os.environ.get("XLA_FLAGS", "")
for flag in ("--xla_force_host_platform_device_count=8",
             "--xla_backend_optimization_level=0",
             "--xla_llvm_disable_expensive_passes=true"):
    if flag.split("=")[0].lstrip("-") not in flags:
        flags += " " + flag
os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

jax.config.update("jax_threefry_partitionable", True)

# A program is compiled once a test process. The engine's programs are
# closures jitted per instance, so JAX's in-memory cache never hits across
# two engines of one shape; the persistent cache does, and a file that
# builds the same tiny engine in case after case pays XLA's CPU backend
# once (thresholds 0 and -1: the tiny programs are kept too). A hit skips
# the backend only: every case still traces and lowers its program, and a
# different program has a different key and compiles. A read that fails
# falls back to compiling, with a warning (jax/_src/compiler._cache_read).
# - Nothing depends on an earlier run: the directory is made empty for
#   this process and removed when it exits.
# - One writer a directory (JAX 0.9.0 writes an entry with a bare
#   write_bytes and takes no lock): each pytest process, so each xdist
#   worker, has its own, and the children a test spawns run with the
#   cache off, through the two variables below. Set after `import jax`
#   they no longer reach this process, whose values are pinned by
#   jax.config; a child reads JAX_ENABLE_COMPILATION_CACHE=false itself,
#   and utils/compile_cache.configure(), here or in a child, sets no path
#   where JAX_COMPILATION_CACHE_DIR names one. So a tier-1 run neither
#   reads nor writes <checkout>/.jax_cache.
# - Compile-count pins still pin: prof.compiles counts JAX's
#   backend-compile event, which wraps the read as it wraps the compile,
#   and jit cache sizes are the in-memory cache.
# The AOT compiles for a described chip (test_chip_compile*.py) write
# entries that cannot be read back without a chip: those files turn the
# cache off around them (chip_compile_kit.no_compile_cache).
_COMPILE_CACHE = tempfile.mkdtemp(prefix="cake_t1_jax_cache_")
atexit.register(shutil.rmtree, _COMPILE_CACHE, ignore_errors=True)
jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE)
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _COMPILE_CACHE
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


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
