"""Repository-wide pytest hooks: a time limit on every test's call phase.

Each test runs under ``DEFAULT_TIME_LIMIT_S`` unless it carries
``@pytest.mark.time_limit(seconds)``.  When the limit passes, SIGALRM
raises ``TimeLimitExceeded`` in the test, which then fails with its own
name and its limit.  Python runs that handler only once control returns
from native code, so a compile or a native hang can outlast it; a
``faulthandler`` backstop therefore dumps every thread's stack and ends
the process a quarter of the limit later.  Under xdist the controller
then reports the test that was running as failed and starts a new worker.
"""

import faulthandler
import os
import signal
import threading

import pytest

# the slowest tests take 60-112 s of a full run under six xdist workers
DEFAULT_TIME_LIMIT_S = 180.0
BACKSTOP_FACTOR = 1.25

_STDERR_FD = pytest.StashKey[int]()


class TimeLimitExceeded(BaseException):
    """Raised in a test that outlives its limit.  A BaseException, so that
    neither ``except Exception`` in the test nor hypothesis (which would
    shrink, rerunning the slow example) swallows it."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "time_limit(seconds): the test's time limit, in place of the "
        f"default {DEFAULT_TIME_LIMIT_S:g} s")
    # the real stderr for the backstop's dump: output capture is off
    # here, and takes over fd 2 while a test runs
    config.stash[_STDERR_FD] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_STDERR_FD])


def time_limit_of(item) -> float:
    marker = item.get_closest_marker("time_limit")
    return float(marker.args[0]) if marker else DEFAULT_TIME_LIMIT_S


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    limit = time_limit_of(item)
    on_main = threading.current_thread() is threading.main_thread()

    def expire(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} exceeded its time limit of {limit:g} s")

    if on_main:
        previous = signal.signal(signal.SIGALRM, expire)
        signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit * BACKSTOP_FACTOR, exit=True,
                                      file=item.config.stash[_STDERR_FD])
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        if on_main:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)
