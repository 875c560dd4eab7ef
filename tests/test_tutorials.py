"""Tutorial smoke tests: every tutorial must run green end-to-end.

Each tutorial is a standalone script that bootstraps its own virtual
8-device CPU mesh, so they run as subprocesses with a clean environment
(this process is already pinned to 8 virtual devices by conftest, which is
compatible — the bootstrap re-applies the same flags).
"""

import glob
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TUTORIALS = sorted(
    glob.glob(os.path.join(_REPO, "tutorials", "[0-9][0-9]-*.py")))


def test_tutorials_exist():
    names = [os.path.basename(t)[:2] for t in _TUTORIALS]
    assert names == [f"{i:02d}" for i in range(1, 11)], names


@pytest.mark.parametrize(
    "script", _TUTORIALS, ids=[os.path.basename(t) for t in _TUTORIALS])
def test_tutorial_runs(script, test_limit_s):
    """What only this test shows of a tutorial is that the script runs and
    ends in " ok". Tutorial 10's three modes and its scanned loop are held
    token for token by ``tests/test_qwen_e2e.py`` at TP=4 and three tokens
    (``test_generation_matches_xla_golden``,
    ``test_serve_scanned_matches_serve``), so the script runs them at TP=2 and
    two tokens."""
    # Under the limit of tests/conftest.py, so a tutorial that hangs is
    # reaped by its test and not orphaned when the limit ends the worker.
    r = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=test_limit_s - 30, cwd=_REPO)
    assert r.returncode == 0, (
        f"{os.path.basename(script)} failed:\n{r.stdout[-2000:]}\n"
        f"{r.stderr[-2000:]}")
    assert " ok" in r.stdout.splitlines()[-1]
