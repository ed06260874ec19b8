"""The documents that describe the system as it is name files that exist,
and the option census does not grow unseen.

Read: README.md and docs/*.md. Not read: the histories (CHANGES.md,
ROADMAP.md, PERF.md), which name what was deleted on purpose."""
import glob
import os
import re

import pytest

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
PACKAGE = os.path.join(REPO, "transmogrifai_tpu")

DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_SOURCE_EXT = (".py", ".md", ".cpp")
_DATA_EXT = (".json", ".jsonl")
#: where a relative path in a document may start from
_ROOTS = ("", "transmogrifai_tpu", "docs")


def _package_sources():
    for d, _, fs in os.walk(PACKAGE):
        for f in fs:
            if f.endswith((".py", ".cpp")):
                yield os.path.join(d, f)


def _repo_basenames():
    names = set()
    for d, dirs, fs in os.walk(REPO):
        dirs[:] = [x for x in dirs if not x.startswith(".")
                   and x not in ("__pycache__", "chiprun_out")]
        names.update(fs)
    return names


def _file_tokens(text):
    """Back-quoted tokens that look like one file of this repository:
    a known extension, no blank, no pattern character, not absolute;
    a trailing ``:line`` or ``::name`` is dropped."""
    for tok in sorted(set(re.findall(r"`([^`\n]+)`", text))):
        tok = re.sub(r":[\d,\-–]+$", "", tok.split("::")[0])
        if (tok.endswith(_SOURCE_EXT + _DATA_EXT) and " " not in tok
                and not tok.startswith(("/", "~"))
                and not any(c in tok for c in "*<>{}$")):
            yield tok


@pytest.fixture(scope="module")
def repo_basenames():
    return _repo_basenames()


@pytest.fixture(scope="module")
def package_text():
    return "\n".join(open(p, encoding="utf-8").read()
                     for p in _package_sources())


@pytest.mark.parametrize("doc", DOCS)
def test_files_a_document_names_exist(doc, repo_basenames, package_text):
    """A path resolves against the repo root, the package or docs/. A bare
    source name is some file's name. A bare ``.json`` / ``.jsonl`` name is
    a file at the root or one the program writes (the package's source
    holds the name)."""
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    missing = []
    for tok in _file_tokens(text):
        if any(os.path.exists(os.path.join(REPO, root, tok))
               for root in _ROOTS):
            continue
        if "/" not in tok and (
                tok in repo_basenames if tok.endswith(_SOURCE_EXT)
                else f'"{tok}"' in package_text):
            continue
        missing.append(tok)
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_option_census_does_not_grow():
    """Distinct ``TG_*`` names under transmogrifai_tpu/ (ROADMAP D3). A PR
    that adds an option edits this number in plain sight; one that removes
    an option lowers it."""
    names = set()
    for p in _package_sources():
        with open(p, encoding="utf-8") as fh:
            names.update(re.findall(r"TG_[A-Z0-9_]+", fh.read()))
    assert len(names) <= 94, sorted(names)
