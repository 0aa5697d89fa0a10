"""The per-test time limit that `conftest.py` arms around every test."""
import pathlib
import re
import shutil
import signal
import sys
import textwrap
import time

import pytest

from support import run_child, src_env, time_limit

HERE = pathlib.Path(__file__).resolve().parent


def test_time_limit_fails_what_runs_past_it():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="time limit of 1 s"):
        with time_limit(1.0, "sleeper"):
            time.sleep(30)
    assert time.monotonic() - t0 < 5


@pytest.mark.parametrize("runs_past", [False, True], ids=["within", "past"])
def test_time_limit_puts_back_the_enclosing_limit(runs_past):
    """Afterwards the SIGALRM handler is the one from before, and the
    enclosing limit (this test's own) is armed again."""
    before = signal.getsignal(signal.SIGALRM)
    try:
        with time_limit(0.2, "inner"):
            assert signal.getsignal(signal.SIGALRM) is not before
            if runs_past:
                time.sleep(5)
    except pytest.fail.Exception:
        assert runs_past
    assert signal.getsignal(signal.SIGALRM) is before
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0


def test_limit_marker_fails_its_test_with_a_traceback(tmp_path):
    """End to end through pytest: a test marked `limit(1)` that sleeps for a
    minute fails in about a second, with the limit's message and a dump of
    the threads' stacks; the next test still runs."""
    for name in ("conftest.py", "support.py"):
        shutil.copy(HERE / name, tmp_path / name)
    (tmp_path / "test_sleeper.py").write_text(textwrap.dedent("""
        import time
        import pytest

        @pytest.mark.limit(1)
        def test_sleeps():
            time.sleep(60)

        def test_after():
            pass
        """))
    out = run_child([sys.executable, "-m", "pytest", "-q", "--durations=1",
                     "-p", "no:cacheprovider", "-W",
                     "ignore::pytest.PytestUnknownMarkWarning", str(tmp_path)],
                    timeout=120, env=src_env())
    assert out.returncode == 1, out.stdout[-3000:] + out.stderr[-2000:]
    assert "1 failed, 1 passed" in out.stdout, out.stdout[-3000:]
    assert "ran past its time limit of 1 s" in out.stdout
    assert "Current thread" in out.stdout  # faulthandler's stack dump
    took = re.search(r"([0-9.]+)s call .*test_sleeps", out.stdout)
    assert took and float(took.group(1)) < 3, out.stdout[-3000:]
