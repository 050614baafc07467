"""README and docs/ name no file that is not there: every back-quoted token
that is a path of this repo exists."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(glob.glob("docs/*.md", root_dir=REPO))
DIRS = ("megatron_tpu/", "tools/", "tests/", "tasks/", "benchmark/", "docs/",
        "examples/")
# a root-level record in capitals: PERF.md, BENCHMARK.json, PERF_LEDGER.jsonl
RECORD = re.compile(r"[A-Z][A-Z_0-9a-z]*\.(md|json|jsonl)$")


def repo_paths(text):
    """Back-quoted tokens that claim to be paths of the repo, `::name` and
    `:line` stripped. Commands, globs and placeholders (a space, `*`, `<`,
    `{`) and bare file names are not paths."""
    paths = set()
    for token in re.findall(r"`([^`\n]+)`", text):
        if any(c in token for c in "*<{ "):
            continue
        token = re.sub(r":\d+(-\d+)?$", "", token.split("::")[0])
        if token.startswith(DIRS) or RECORD.match(token):
            paths.add(token)
    return paths


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        paths = repo_paths(f.read())
    assert paths, f"{doc} names no path: the pattern has stopped matching"
    missing = sorted(p for p in paths
                     if not os.path.exists(os.path.join(REPO, p)))
    assert not missing, f"{doc} names files that do not exist: {missing}"
