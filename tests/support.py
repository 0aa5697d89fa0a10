"""Helpers shared by the test files: a per-test time limit and a bounded
subprocess run.

``time_limit`` is what ``conftest.py`` arms around every test; a test that
needs more than ``DEFAULT_LIMIT_S`` asks for it with
``@pytest.mark.limit(seconds)``. ``run_child`` runs a child interpreter in a
process group of its own, so a child that hangs (or leaves workers behind)
fails its one test in bounded time instead of holding the suite's worker.
"""
from __future__ import annotations

import contextlib
import faulthandler
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

# about 3x the slowest test, 115 s under `-n 6 --dist loadfile` on 8 CPU cores
DEFAULT_LIMIT_S = 360.0


def limit_of(item) -> float:
    """The time limit of a collected test: its ``limit`` marker, else the
    default."""
    marker = item.get_closest_marker("limit")
    return float(marker.args[0]) if marker is not None else DEFAULT_LIMIT_S


def _dump_traceback():
    try:
        faulthandler.dump_traceback(file=sys.stderr)
    except (AttributeError, ValueError, OSError):  # stderr has no real fd
        faulthandler.dump_traceback(file=sys.__stderr__)


@contextlib.contextmanager
def time_limit(seconds: float, what: str = "test"):
    """Fail ``what`` with a traceback once it has run ``seconds`` of wall time.

    SIGALRM interrupts the main thread, so this arms nothing off the main
    thread or where ``signal.setitimer`` does not exist. An enclosing limit
    is put back afterwards with the time it has left.
    """
    if (not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def on_alarm(signum, frame):
        _dump_traceback()
        pytest.fail(f"{what} ran past its time limit of {seconds:g} s")

    start = time.monotonic()
    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    outer, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)
        if outer > 0:
            left = outer - (time.monotonic() - start)
            signal.setitimer(signal.ITIMER_REAL, max(left, 1e-3))


def src_env(**extra) -> dict:
    """The current environment with the checkout's ``src`` on PYTHONPATH."""
    env = dict(os.environ, **extra)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return env


def run_child(argv, timeout: float, env=None) -> subprocess.CompletedProcess:
    """``subprocess.run(argv, capture_output=True, text=True)`` bounded by
    ``timeout``: past it, the child's whole process group is killed and the
    test fails with the child's stderr so far."""
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        pytest.fail(f"child ran past its timeout of {timeout:g} s; "
                    f"its stderr so far:\n{err[-4000:]}")
    finally:
        # whatever the child left behind in its group (workers it spawned
        # and could not stop) goes too, and so does the child itself if the
        # wait was interrupted, e.g. by the test's time limit
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
    return subprocess.CompletedProcess(argv, proc.returncode, out, err)
