"""The time limit tests/conftest.py gives every test."""

import re
import signal
import time

import pytest


def test_a_test_that_outlasts_its_limit_fails_with_its_name(request):
    assert signal.getsignal(signal.SIGALRM) is not signal.SIG_DFL
    signal.alarm(1)         # the fixture's handler, the limit cut to a second
    with pytest.raises(TimeoutError, match=re.escape(request.node.nodeid)):
        time.sleep(10)
