"""Every repository path a document names resolves.

One case per document (README.md, BASELINE.md, cfg/config.yaml and
docs/*.md; docs/acceptance/ is a record of past runs and is left out):

- a token with a directory from ``DIRS`` and a suffix from ``SUFFIXES``
  must exist at that path;
- a bare backticked ``name.py``, and the script of a ``python name.py``
  command, must exist under that basename somewhere in the tree (what
  git ignores is not searched).
"""

import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

DOCUMENTS = ["README.md", "BASELINE.md", "cfg/config.yaml"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md")
)

DIRS = (
    "scripts", "tests", "docs", "benchmarks", "cfg", "examples",
    "marl_distributedformation_tpu",
)
SUFFIXES = ("py", "md", "yaml", "json")
_PATH = re.compile(
    r"(?<![\w/.<>{}*-])((?:%s)/[\w./-]*\.(?:%s))(?![\w/*{<-])"
    % ("|".join(DIRS), "|".join(SUFFIXES))
)
_BARE = re.compile(r"`([\w-]+\.py)`")
_COMMAND = re.compile(r"\bpython3? +(?:-[A-Za-z] +)*([\w./-]+\.py)\b")
# Run-time outputs, not sources: git ignores them.
_NOT_SEARCHED = {"__pycache__", "logs", "outputs", "tensorboard", "chiprun_out"}


@pytest.fixture(scope="module")
def basenames():
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".") and d not in _NOT_SEARCHED
        ]
        names.update(files)
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_paths_resolve(document, basenames):
    text = (REPO / document).read_text()
    commands = _COMMAND.findall(text)
    paths = set(_PATH.findall(text)) | {c for c in commands if "/" in c}
    scripts = set(_BARE.findall(text)) | {
        c for c in commands if "/" not in c
    }
    missing = sorted(p for p in paths if not (REPO / p).exists())
    missing += sorted(s for s in scripts if s not in basenames)
    assert not missing, f"{document} names paths that do not exist: {missing}"
