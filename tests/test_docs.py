"""The documents name only files that exist: a path a PR deletes and
leaves cited in README.md or PERF.md fails here, and so does a test file
a PR moves and leaves cited in ROADMAP.md or in pytest.ini's comment
(their `tests/*.py` alone: the roadmap also tells of files that went)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIRS = ("ray_tpu/", "chipbench/", "benchmarks/", "scripts/", "tests/")
PLACEHOLDER = set("<*{")


def cited_paths(text):
    """Paths inside back-quotes: a word under one of DIRS, or a bare
    `*.py` name; `:line`, `::test` and trailing punctuation are cut, a
    word with a placeholder is skipped."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.split("::")[0]
            word = re.sub(r":[\d,:-]*$", "", word).rstrip(".,;:)")
            if PLACEHOLDER & set(word):
                continue
            if word.startswith(DIRS) or re.fullmatch(r"\w+\.py", word):
                yield word


def cited_test_files(text):
    """`tests/<...>.py` wherever it stands, back-quoted or in a comment."""
    return re.findall(r"\btests/[\w/]+\.py\b", text)


@pytest.mark.parametrize("doc,cited", [
    pytest.param(doc, cited, id=doc) for doc, cited in (
        ("README.md", cited_paths), ("PERF.md", cited_paths),
        ("ROADMAP.md", cited_test_files), ("pytest.ini", cited_test_files))])
def test_every_cited_path_exists(doc, cited):
    with open(os.path.join(REPO, doc)) as f:
        paths = sorted(set(cited(f.read())))
    assert paths, f"{doc} cites no path: the pattern has rotted"
    # a bare name is a root script, or a module of a directory the
    # sentence has named
    modules = {n for d in DIRS for _, _, names in os.walk(os.path.join(REPO, d))
               for n in names}
    missing = [p for p in paths
               if not (os.path.exists(os.path.join(REPO, p)) or p in modules)]
    assert missing == [], f"{doc} cites paths that do not exist: {missing}"
