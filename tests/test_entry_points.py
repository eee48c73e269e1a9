"""Every ``python -m <module>`` the documentation names exists and parses
its arguments: ``--help`` exits 0 for each one.

The commands are collected from the README, the CI workflow and the
verify recipe, so a module that is deleted or renamed while a document
still names it fails here instead of in a reader's shell.
"""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (
    "README.md",
    os.path.join(".github", "workflows", "ci.yml"),
    os.path.join(".claude", "skills", "verify", "SKILL.md"),
)
# third-party tools the documents also run with ``python -m``
NOT_OURS = {"pip", "pytest"}


def _documented_modules() -> list[str]:
    found = set()
    for doc in DOCS:
        with open(os.path.join(REPO, doc)) as f:
            text = f.read()
        # a command may wrap between ``-m`` and the module name
        found.update(re.findall(r"python -m\s+([A-Za-z_][\w.]*)", text))
    return sorted(found - NOT_OURS)


MODULES = _documented_modules()


def test_documents_name_entry_points():
    assert "repro.launch.train" in MODULES, MODULES


@pytest.mark.parametrize("module", MODULES)
def test_documented_module_help_exits_zero(module):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", module, "--help"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "usage" in out.stdout.lower(), out.stdout[-2000:]
