# NOTE: no device-count flag here — smoke tests and benches must see 1 CPU
# device.  Only launch/dryrun.py requests 512 placeholder devices (and only
# when run as a script).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# XLA:CPU takes its YNNPACK fusions only on some host CPUs, and they round
# f32 results differently with an operand's row count: the bucket-invariance
# tests (same prompt, different padding) would then hold on one host and
# fail on another.  Off, before the first backend initialization.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_cpu_experimental_ynn_fusion_type=").strip()

import jax
import numpy as np
import pytest

# pytest-timeout is a dev-extra dependency; when absent (offline images),
# provide a minimal SIGALRM-based fallback so the `timeout` ini default in
# pyproject.toml and per-test `timeout` markers still guard against wedged
# tests (main thread, POSIX only — the no-op cases just run unguarded).
try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False


if not _HAVE_PYTEST_TIMEOUT:
    import signal
    import threading

    def pytest_addoption(parser):
        parser.addini("timeout", "per-test timeout in seconds "
                                 "(conftest SIGALRM fallback)", default="0")

    def pytest_configure(config):
        config.addinivalue_line(
            "markers",
            "timeout(seconds): per-test timeout override "
            "(conftest SIGALRM fallback)")

    @pytest.fixture(autouse=True)
    def _timeout_guard(request):
        limit = 0.0
        try:
            limit = float(request.config.getini("timeout") or 0)
        except (TypeError, ValueError):
            pass
        marker = request.node.get_closest_marker("timeout")
        if marker is not None and marker.args:
            limit = float(marker.args[0])
        if (limit <= 0 or not hasattr(signal, "SIGALRM")
                or threading.current_thread()
                is not threading.main_thread()):
            yield
            return

        def _alarm(signum, frame):
            pytest.fail(f"test exceeded {limit:.0f}s timeout "
                        f"(conftest SIGALRM fallback)", pytrace=False)

        old = signal.signal(signal.SIGALRM, _alarm)
        signal.alarm(int(limit))
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
