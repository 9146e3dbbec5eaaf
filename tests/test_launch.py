"""Launcher-level coverage: the serve CLI end to end, in a
subprocess."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH="src")


@pytest.mark.slow
def test_serve_cli(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--dataset", "tiny",
         "--method", "2dreach-comp", "--queries", "50", "--verify", "20"],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "verified" in r.stdout

