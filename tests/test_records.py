"""The repo gives ONE account of its speed and of its own files.

- The README's benchmark table lists exactly the cells ``BENCHMARK.json``
  declares (the driver measures those and nothing else), and every
  configuration file the benchmark names exists.
- The documents name no file that is not in the tree: a passage that
  outlives the file it describes (a harness, a script, a record) is how a
  second, stale account of speed survives.
"""

from __future__ import annotations

import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "docs/design.md", "docs/tutorial.md", "docs/migration.md", "docs/lint.md"]
_EXT = (".py", ".json", ".md", ".sh")


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        return fh.read()


def test_readme_lists_every_cell():
    bench = json.loads(_read("BENCHMARK.json"))
    text = _read("README.md")
    section = text[text.index("## Benchmarks"):]
    section = section[: section.index("\n## ", 1)]
    # a table row of a cell: | `<cell>` | `<config>`: ... | size | chips | ...
    rows = dict(re.findall(r"^\| `([^`]+)` \|(.*)$", section, flags=re.M))
    assert sorted(rows) == sorted(w["name"] for w in bench["workloads"])
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        row = rows[w["name"]]
        assert w["config"] in configs and f"`{w['config']}`" in row, (w["name"], row)
        assert f"| {w['chips']} |" in row, (w["name"], row)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]


def _named_files(text):
    """The file names a document back-quotes: words of a `code` span that
    end in a source or record extension, a ``:line`` or ``::test`` tail
    dropped, and that begin with a top-level entry of this repo.  A bare
    name (``script.py``, a module's basename) and a package-relative path
    (``core/fuse.py``) say too little about where they point to be held."""
    tops = {e for e in os.listdir(ROOT) if not e.startswith(".")}
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.strip("()[],;\"'")
            word = re.sub(r"(::[\w\[\]\-.,]+|:[\d\-,:]+)$", "", word).rstrip(".:")
            if word.endswith(_EXT) and "/" in word and word.split("/", 1)[0] in tops:
                yield word


@pytest.mark.parametrize("doc", DOCS)
def test_documents_name_existing_files(doc):
    """Every back-quoted path that starts at the root of this repo exists
    (``<x>``, ``*`` and ``{a,b}`` stand for any name).  HeAT v0.5.1's own
    paths carry their ``heat/`` prefix or stand outside back quotes, and
    are not this repo's to hold."""
    words = sorted(set(_named_files(_read(doc))))
    missing = [
        w for w in words
        if not glob.glob(os.path.join(ROOT, re.sub(r"<[^>]*>|\{[^}]*\}", "*", w)))
    ]
    assert missing == [], f"{doc} names files that are not in the tree: {missing}"
