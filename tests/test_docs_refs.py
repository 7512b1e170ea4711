"""README.md and docs/*.md name only what the tree holds.

A document that sells a module, a test or a `make` recipe that a PR
removed sends its reader to nothing. Each case reads one document and
checks every path it names under `fengshen_tpu/`, `tests/`,
`benchmarks/` and `docs/` (with `::test_name` where given), and every
`make <target>` it back-quotes or puts in a code block.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: a path under one of the four roots, optionally `::test_name`; a
#: placeholder (`<f>`) or a sentence's punctuation ends it
_PATH = re.compile(
    r"(?<![\w/.-])((?:fengshen_tpu|tests|benchmarks|docs)/"
    r"[\w./*-]*[\w/*])(?:::(\w+))?")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_MAKE = re.compile(r"(?:^|[\s`;&(])make +([a-z][\w-]*)")


def _exists(path: str) -> bool:
    # `tests/test_examples*`: one match is enough
    return bool(glob.glob(os.path.join(REPO, path)))


def _make_targets() -> set:
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = []
    for m in _PATH.finditer(text):
        path, test_name = m.group(1), m.group(2)
        line = text.count("\n", 0, m.start()) + 1
        if not _exists(path):
            missing.append(f"{document}:{line}: {path}")
        elif test_name and path.endswith(".py"):
            with open(os.path.join(REPO, path)) as f:
                if not re.search(rf"^\s*def {test_name}\b", f.read(),
                                 re.M):
                    missing.append(
                        f"{document}:{line}: {path}::{test_name}")
    targets = _make_targets()
    for code in _CODE.finditer(text):
        for m in _MAKE.finditer(code.group(0)):
            if m.group(1) not in targets:
                line = text.count("\n", 0, code.start() + m.start()) + 1
                missing.append(f"{document}:{line}: make {m.group(1)}")
    assert not missing, "names what the tree does not hold:\n" + \
        "\n".join(missing)
