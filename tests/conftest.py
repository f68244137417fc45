"""Test backend: an 8-device virtual CPU mesh.

The reference's CI needs real GPUs and two real machines (SURVEY.md §4); the TPU build
tests sharding semantics on a faked multi-chip backend instead:
``--xla_force_host_platform_device_count=8`` gives every test a deterministic 8-device
mesh with real XLA collectives. Must run before the first ``import jax``.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import threading

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on an 8-device virtual CPU mesh
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("AUTODIST_IS_TESTING", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Pin the backend NOW: initialization is otherwise lazy, and a test module
# that adjusts XLA_FLAGS for its own subprocesses (imported before the first
# device touch) would silently re-shape every later test's "8-device" mesh.
assert len(jax.devices()) == 8, jax.devices()

import pytest  # noqa: E402


def pytest_addoption(parser):
    # Reference conftest.py:4-17 gates integration tests behind --run-integration; kept
    # for workflow parity, though our integration tier runs fine on the CPU mesh.
    parser.addoption("--run-integration", action="store_true", default=False,
                     help="run tests marked integration")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-integration"):
        return
    skip = pytest.mark.skip(reason="needs --run-integration")
    for item in items:
        if "integration" in item.keywords and item.get_closest_marker("integration"):
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _graftsan_thread_fence():
    """graftsan ``threads`` fence: with ``AUTODIST_SANITIZE=threads`` armed,
    a test leaking a live non-daemon thread past its own teardown fails with
    every survivor's name and current stack (testing/sanitizer.py). Disarmed
    (the default), the fixture is a no-op yield."""
    from autodist_tpu.testing import sanitizer
    if "threads" not in sanitizer.modes():
        yield
        return
    with sanitizer.thread_fence(grace_s=2.0):
        yield


# Seconds a test may take, set-up and teardown of its other fixtures aside:
# three times the longest case of the suite (CHANGES.md, PR 45) and the limit
# the multi-process tests give their subprocesses. The driver's window is for
# the whole suite (ROADMAP D12); without a clock of its own a test that hangs
# spends all of it and the count does not say which test it was.
LIMIT = 300.0


@contextlib.contextmanager
def clock(nodeid: str):
    """Fail the test ``nodeid`` once it has run ``LIMIT`` seconds, with every
    thread's stack on stderr first. ``SIGALRM`` reaches the main thread only
    and only between bytecodes: a test stuck inside one native call fails when
    that call returns. No timer is left armed on the way out."""
    if not hasattr(signal, "SIGALRM") \
            or threading.current_thread() is not threading.main_thread():
        yield
        return
    limit = LIMIT

    def expired(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__)
        pytest.fail(f"{nodeid} exceeded {limit:g} s", pytrace=False)

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _clock(request):
    with clock(request.node.nodeid):
        yield
