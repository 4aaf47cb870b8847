"""Tests run on the REAL device count (1 CPU device) — the 512-device flag
is set only by launch/dryrun.py (and must never leak into tests).

Exception: the multi-device tier-1 leg (tests/test_multidevice.py) opts in
explicitly with MATPIM_MULTIDEVICE=1 + an 8-virtual-device XLA flag so the
sharded executor paths run on CPU CI; everything else keeps the guard."""
import os

import pytest

assert ("xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")
        or os.environ.get("MATPIM_MULTIDEVICE") == "1"), \
    "tests must not run with forced host device count " \
    "(set MATPIM_MULTIDEVICE=1 for the sharded-execution leg)"


@pytest.fixture
def single_retry():
    """Bounded retry for wall-clock-sensitive assertions.

    Timing assertions (perf ratios, overhead bounds) can fail on a noisy
    scheduler without any code being wrong. ``single_retry(check)`` runs the
    ``check`` callable; on ``AssertionError`` it retries exactly ONCE, and a
    second failure raises loudly with both messages — a real regression
    fails twice, a scheduler hiccup doesn't. Never use it on correctness
    assertions: only the measurement may be re-taken, not the semantics.
    """
    def run(check):
        try:
            return check()
        except AssertionError as first:
            try:
                return check()
            except AssertionError as second:
                raise AssertionError(
                    f"timing check failed twice (not scheduler noise): "
                    f"first: {first}; retry: {second}") from second
    return run

# Persistent XLA compilation cache: the model-smoke/serve tests are dominated
# by jit compiles, so repeat local runs and cache-restoring CI get much
# faster. $JAX_COMPILATION_CACHE_DIR overrides the checkout's .jax_cache.
from repro.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
