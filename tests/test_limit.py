"""``conftest._test_limit``: a test that runs past its limit fails alone, with
its name and the stack it was stopped in, and the run goes on."""

import signal
import time

import pytest

import conftest


def test_a_test_past_its_limit_fails_with_its_name_and_its_stack(request):
    """The fixture's own handler, with the timer it armed for THIS test
    brought down from ``TEST_LIMIT_S`` to 50 ms."""
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= conftest.TEST_LIMIT_S
    signal.setitimer(signal.ITIMER_REAL, 0.05)
    with pytest.raises(pytest.fail.Exception) as stopped:
        time.sleep(5)
    said = str(stopped.value)
    assert request.node.nodeid in said and "ran past its limit" in said
    assert "time.sleep(5)" in said                      # where it was waiting


def test_the_limit_is_cleared_when_a_test_ends():
    """Runs after the one above: what that test's timer left behind would fail this one."""
    time.sleep(0.1)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > conftest.TEST_LIMIT_S - 5
