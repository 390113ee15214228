"""The documents name what exists.

The files that describe the tree as it IS (``README.md``, ``docs/*.md``,
the verify skill, the CI workflow; not the histories ``CHANGES.md``,
``ROADMAP.md``, ``PERF.md``, and nothing under ``benchmark/``) are held
to the tree:

- every repository path one of them names in backticks, links to or runs
  as a command exists among the tracked files;
- every ``GGRS_*`` environment name one of them mentions is read by a
  tracked source file, and every ``GGRS_*`` name the package reads is
  mentioned in ``docs/`` or ``README.md``.

A deletion that leaves a signpost to nothing, or an environment name
that nobody can find out about, fails here.
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GUIDES = ("README.md",) + tuple(sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
))
DOCUMENTS = GUIDES + (
    ".claude/skills/verify/SKILL.md",
    ".github/workflows/ci.yml",
)

PATH = re.compile(
    r"(?<![\w./-])((?:[\w.-]+/)*[\w.-]+\.(?:py|md|json|cpp|yml))(?!\w)"
)
NAME = re.compile(r"(?<![A-Z0-9_])GGRS_[A-Z0-9_]*[A-Z0-9]")

# What a documented command WRITES (by directory, or by the name the
# example gives it) and the one file of the upstream project that a
# comparison table cites: named, and rightly absent from the tree.
WRITTEN_UNDER = ("obs-artifacts/", "obs-out/", "chiprun_out/")
WRITTEN_AS = {"trace.json", "merged.json", "front_door_slo.json"}
UPSTREAM = {".github/workflows/rust.yml"}


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def tracked():
    """The files of the checkout, without what ``.gitignore`` leaves out
    (read from the disk: a checkout may come without its ``.git``)."""
    ignored = {".git/"} | set(read(".gitignore").split())
    out = []
    for d, dirs, files in os.walk(ROOT):
        dirs[:] = [x for x in dirs if x + "/" not in ignored]
        out += [os.path.relpath(os.path.join(d, f), ROOT) for f in files]
    return tuple(sorted(out))


def named_paths(rel):
    text = read(rel)
    if rel.endswith(".md"):
        spans = re.findall(r"```.*?```|`[^`\n]+`", text, re.S)
        spans += re.findall(r"\]\(([^)#\s]+)", text)
    else:
        spans = [text]
    return sorted({m for span in spans for m in PATH.findall(span)})


def exists(path, rel):
    """``path`` as ``rel`` may mean it: from the root, from the package,
    beside the document, the tail of a longer path (``serve/batch.py``)
    or a bare file name."""
    if path.startswith(WRITTEN_UNDER) or path in UPSTREAM:
        return True
    if os.path.basename(path) in WRITTEN_AS:
        return True
    here = os.path.normpath(os.path.join(os.path.dirname(rel), path))
    return any(
        f == here or ("/" + f).endswith("/" + path) for f in tracked()
    )


@pytest.mark.parametrize("rel", DOCUMENTS)
def test_every_path_a_document_names_exists(rel):
    names = named_paths(rel)
    assert names, f"{rel} names no file: the extraction is broken"
    missing = [p for p in names if not exists(p, rel)]
    assert not missing, f"{rel} names files that do not exist: {missing}"


@functools.lru_cache(maxsize=None)
def names_in(rels):
    return sorted({m for rel in rels for m in NAME.findall(read(rel))})


def sources(prefix=""):
    return tuple(
        f for f in tracked()
        if f.startswith(prefix) and f.endswith((".py", ".cpp"))
        and f != "tests/test_repo_records.py"
    )


@pytest.mark.parametrize("name", names_in(DOCUMENTS))
def test_every_environment_name_a_document_mentions_is_read(name):
    assert name in names_in(sources()), (
        f"{name} is documented and no source file reads it"
    )


@pytest.mark.parametrize("name", names_in(sources("bevy_ggrs_tpu/")))
def test_every_environment_name_the_package_reads_is_documented(name):
    assert name in names_in(GUIDES), (
        f"the package reads {name}; neither README.md nor docs/ says so"
    )
