"""Every script the README or the CI lanes name is in the tree.

A file that is deleted while a doc or a lane still names it leaves a
command that fails on its first run.  The paths are read from
`README.md` and `tools/run_ci.sh` at collection time: a `.py` / `.sh`
path under `scripts/`, `tools/` or `examples/`, and a file at the repo
root that a `python` command line runs.
"""
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SOURCES = ("README.md", "tools/run_ci.sh")
_UNDER_DIR = re.compile(
    r"(?<![\w./-])((?:scripts|tools|examples)/[\w/-]+\.(?:py|sh))\b")
_AT_ROOT = re.compile(r"\bpython3?\s+([\w-]+\.py)\b")


def _named_paths() -> list:
    found = set()
    for src in SOURCES:
        text = (REPO / src).read_text()
        found.update(_UNDER_DIR.findall(text))
        found.update(_AT_ROOT.findall(text))
    return sorted(found)


@pytest.mark.parametrize("path", _named_paths())
def test_named_path_exists(path):
    assert (REPO / path).is_file(), (
        f"{path} is named in {' or '.join(SOURCES)} but not in the tree")
