"""The per-test limit of ``tests/conftest.py``, driven as a child pytest.

Each child runs files written to ``tmp_path`` beside a ``conftest.py``
that loads the repo's by path, sets its limit to two seconds and
re-exports its hooks: the limit is a constant, not an option, so the
children patch it.
"""

import pathlib
import subprocess
import sys

REPO_CONFTEST = pathlib.Path(__file__).with_name("conftest.py")

CHILD_CONFTEST = f"""
import importlib.util

spec = importlib.util.spec_from_file_location("repo_conftest", {str(REPO_CONFTEST)!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.TEST_LIMIT_S = 2
pytest_configure = repo_conftest.pytest_configure
pytest_runtest_protocol = repo_conftest.pytest_runtest_protocol
pytest_handlecrashitem = repo_conftest.pytest_handlecrashitem
"""

# Longer than the limit: a timer left armed by the last test would end
# the process here.
OUTLIVES_THE_LIMIT = """
def pytest_sessionfinish(session):
    import time
    time.sleep(3)
"""

HANGING = """
import threading

def test_before():
    pass

def test_blocks_for_ever():
    threading.Event().wait()

def test_after():
    pass
"""

QUICK = """
def test_one():
    pass

def test_two():
    pass
"""


def _child_pytest(tmp_path, files, *args, within_s=30):
    for name, text in {"conftest.py": CHILD_CONFTEST, **files}.items():
        (tmp_path / name).write_text(text)
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider", *args, "."],
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=within_s,
    )


def test_a_test_that_blocks_for_ever_ends_the_run_with_its_stack(tmp_path):
    done = _child_pytest(tmp_path, {"test_hanging.py": HANGING}, "-p", "no:xdist")
    assert done.returncode != 0
    assert "Timeout (0:00:02)!" in done.stderr
    assert 'test_hanging.py", line 8 in test_blocks_for_ever' in done.stderr


def test_quick_tests_pass_and_leave_no_timer_armed(tmp_path):
    done = _child_pytest(
        tmp_path,
        {"conftest.py": CHILD_CONFTEST + OUTLIVES_THE_LIMIT, "test_quick.py": QUICK},
        *("-p", "no:xdist"),
    )
    assert done.returncode == 0, done.stdout + done.stderr
    assert "2 passed" in done.stdout
    assert "Timeout" not in done.stderr


def test_under_xdist_a_blocked_test_costs_one_failure_and_one_worker(tmp_path):
    """The driver's distribution, ``--dist loadfile``: the blocked test
    fails by name once, its worker is replaced, and the rest of its file
    and the other file still run."""
    done = _child_pytest(
        tmp_path,
        {"test_hanging.py": HANGING, "test_quick.py": QUICK},
        *("-p", "xdist", "-n", "2", "--dist", "loadfile"),
        within_s=90,  # four interpreters start here, on a machine the suite loads
    )
    assert done.returncode == 1, done.stdout + done.stderr
    assert "1 failed, 4 passed" in done.stdout
    assert done.stdout.count("node down") == 1
    assert "crashed while running 'test_hanging.py::test_blocks_for_ever'" in done.stdout
    assert 'test_hanging.py", line 8 in test_blocks_for_ever' in done.stderr
