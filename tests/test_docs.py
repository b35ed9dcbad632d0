"""The documents name only files that exist: a path a PR deletes and
leaves cited in README.md or PERF.md fails here."""

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


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_every_cited_path_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        paths = sorted(set(cited_paths(f.read())))
    assert paths, f"{doc} cites no path: the pattern has rotted"
    # a bare name is a root script, or a module of a directory the
    # sentence has named
    modules = {n for d in DIRS for _, _, names in os.walk(os.path.join(REPO, d))
               for n in names}
    missing = [p for p in paths
               if not (os.path.exists(os.path.join(REPO, p)) or p in modules)]
    assert missing == [], f"{doc} cites paths that do not exist: {missing}"
