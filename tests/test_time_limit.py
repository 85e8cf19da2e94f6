"""The per-test time limit of the repository's root conftest.py, checked
on inner pytest runs in child processes (so that the inner limits never
replace the outer test's own timer)."""

import pathlib

pytest_plugins = ["pytester"]

ROOT_CONFTEST = pathlib.Path(__file__).resolve().parents[1] / "conftest.py"


def _inner(pytester, body: str):
    pytester.makeconftest(ROOT_CONFTEST.read_text())
    pytester.makepyfile(test_inner=body)


def test_sleep_past_the_limit_fails_by_name_and_limit(pytester):
    _inner(pytester, """
        import time
        import pytest

        @pytest.mark.time_limit(0.5)
        def test_sleeps():
            time.sleep(30)
    """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(failed=1)
    result.stdout.fnmatch_lines([
        "*TimeLimitExceeded: test_inner.py::test_sleeps exceeded its time "
        "limit of 0.5 s*"])
    assert result.duration < 20


def test_under_the_limit_passes(pytester):
    _inner(pytester, """
        import time
        import pytest

        @pytest.mark.time_limit(5)
        def test_quick():
            time.sleep(0.2)

        def test_default_limit():
            pass
    """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(passed=2)


def test_hypothesis_does_not_swallow_the_limit(pytester):
    """The limit ends a hypothesis sweep at once, without shrinking."""
    _inner(pytester, """
        import time
        import pytest
        from hypothesis import given, settings, strategies as st

        @pytest.mark.time_limit(0.5)
        @settings(max_examples=100, deadline=None, database=None)
        @given(st.integers())
        def test_sweep(x):
            time.sleep(0.2)
    """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(failed=1)
    result.stdout.fnmatch_lines(["*exceeded its time limit of 0.5 s*"])
    assert result.duration < 20


def test_native_hang_is_ended_by_the_backstop(pytester):
    """With SIGALRM blocked (as while native code holds the thread), the
    faulthandler backstop ends the worker; xdist names the test that was
    running and the run goes on to the next test."""
    _inner(pytester, """
        import signal
        import time
        import pytest

        @pytest.mark.time_limit(1)
        def test_hangs():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(60)

        def test_after():
            pass
    """)
    result = pytester.runpytest_subprocess(
        "-p", "no:cacheprovider", "-p", "xdist", "-n", "1")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines(
        ["*crashed while running*test_inner.py::test_hangs*"])
    # the backstop's dump shows where the worker hung
    result.stderr.fnmatch_lines(["Timeout (0:00:01.250000)!",
                                 "*test_inner.py*in test_hangs"])
    assert result.duration < 40
