"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh, the same way the reference
simulates multi-machine training with localhost sockets
(/root/reference/tests/distributed/_test_distributed.py) — see SURVEY.md §4.

The suite never touches a chip: ``JAX_PLATFORMS=cpu`` in the environment
works (the tier-1 command sets it), and ``jax.config.update`` below pins the
platform for a bare ``pytest`` too.  A timing taken here is not a speed
number (docs/Testing.md).
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

# Persistent compilation cache: the suite's wall clock is dominated by
# XLA compiles (hundreds of jit variants across growers / shapes).  Where
# JAX_COMPILATION_CACHE_DIR is set the suite uses it like every other
# process; otherwise it keeps its XLA:CPU entries in a fixed directory
# of its own under the checkout's cache, apart from TPU entries, and says
# so through the same variable so that worker subprocesses agree.  Tests
# that need a private directory unset the variable for themselves.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache", "tests_cpu"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from lightgbm_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

import faulthandler  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Per-test hang watchdog: a hung collective / device claim used to eat
# the whole tier-1 870 s budget silently (the outer `timeout -k 10 870`
# kills pytest with NO traceback).  Arm a faulthandler dump per test: any
# test still running after this many seconds dumps all-thread stacks to
# stderr (repeating, non-fatal) so the hang is attributable to a line of
# code.  Same mechanism as lightgbm_tpu.utils.resilience.Watchdog — the
# timer is process-global, so a Watchdog used INSIDE a test takes over
# until it exits (its cancel also clears this per-test timer; acceptable).
FAULTHANDLER_TEST_TIMEOUT_S = 300.0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    faulthandler.dump_traceback_later(FAULTHANDLER_TEST_TIMEOUT_S,
                                      repeat=True)
    yield
    faulthandler.cancel_dump_traceback_later()

# Skip budget (VERDICT r2: a regressing guard skipped instead of failing
# and nobody noticed).  On the standard harness — virtual 8-device CPU
# mesh, full toolchain — exactly two skips are expected: the
# graphviz-executable plotting skip and the R-binding smoke test
# (test_r_binding.py, needs Rscript; its shim-compile/link guard still
# RUNS without R).  Every new skip must either be fixed or the budget
# consciously raised here with a comment.
SKIP_BUDGET = 2
_skips: list = []


def pytest_runtest_logreport(report):
    if report.skipped:
        _skips.append(f"{report.nodeid}: {report.longrepr[2] if isinstance(report.longrepr, tuple) else report.longrepr}")


def pytest_sessionfinish(session, exitstatus):
    # only enforce on the standard full-suite harness (virtual CPU mesh);
    # single-chip TPU runs legitimately skip the 8-device tests
    if jax.default_backend() != "cpu" or len(jax.devices()) < 8:
        return
    if session.config.args and any("::" in a for a in session.config.args):
        return                       # targeted runs, not the full suite
    if len(_skips) > SKIP_BUDGET and exitstatus == 0:
        lines = "\n  ".join(_skips)
        print(f"\nERROR: {len(_skips)} skipped tests exceed the skip "
              f"budget ({SKIP_BUDGET}):\n  {lines}", flush=True)
        session.exitstatus = 1


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(42)


@pytest.fixture(scope="session")
def binary_data():
    """Synthetic binary classification set (sklearn-style, utils.py analog)."""
    rs = np.random.RandomState(0)
    n, f = 4000, 20
    x = rs.randn(n, f)
    logit = x[:, 0] * 1.5 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3] + 0.3 * rs.randn(n)
    y = (logit > 0).astype(np.float32)
    return x, y


@pytest.fixture(scope="session")
def regression_data():
    rs = np.random.RandomState(1)
    n, f = 4000, 15
    x = rs.randn(n, f)
    y = (2.0 * x[:, 0] + x[:, 1] ** 2 - 1.5 * x[:, 2] + 0.1 * rs.randn(n)).astype(np.float32)
    return x, y
