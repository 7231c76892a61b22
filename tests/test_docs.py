"""The documents a reader starts from cite files that exist.

Every backticked token of a document that reads as a path of this repo
(a known file suffix, or a trailing ``/`` under a directory of the repo)
must name a file or directory of the tree, or a file the program itself
writes at run time (its name stands in the program's sources). The
histories (``CHANGES.md``, ``ROADMAP.md``'s "Recent", ``PERF.md``'s
Findings and after) are exempt: they speak of what was.
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SUFFIXES = (".py", ".md", ".json", ".jsonl", ".yaml", ".txt", ".sh")
# where a relative path may start: the checkout, or the package
BASES = ("", "imaginaire_tpu")


def _read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def _perf_sections_1_to_5():
    text = _read("PERF.md")
    return text[text.index("## 1. "):text.index("## 6. ")]


DOCUMENTS = {
    "README.md": lambda: _read("README.md"),
    "PARITY.md": lambda: _read("PARITY.md"),
    "PERF.md sections 1-5": _perf_sections_1_to_5,
    ".claude/skills/verify/SKILL.md":
        lambda: _read(".claude/skills/verify/SKILL.md"),
}


@functools.lru_cache(maxsize=None)
def _program_sources():
    """Every source the program runs, as one string: a name in it is a
    name the program may write."""
    paths = glob.glob(os.path.join(ROOT, "*.py"))
    for top in ("imaginaire_tpu", "scripts", "benchmark"):
        paths += glob.glob(os.path.join(ROOT, top, "**", "*.py"),
                           recursive=True)
    return "\n".join(open(p).read() for p in sorted(paths))


@functools.lru_cache(maxsize=None)
def _tracked_basenames():
    names = set()
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames
                       if not d.startswith(".") or d == ".claude"]
        names.update(filenames)
    return names


def cited_paths(text):
    """The backticked tokens of ``text`` that read as repo paths, each
    cut to the path itself (no ``:line``, ``::test``, arguments)."""
    for token in re.findall(r"`([^`\n]+)`", text):
        words = token.split()
        word = words[0] if words else ""
        if word in ("python", "python3") and len(words) > 1:
            word = words[1]
        word = word.split("::")[0]
        word = re.sub(r":[\w,~\-:]*$", "", word).strip("(),.;")
        if not word or word[0] in "/-<$~" or re.search(r"[<>{}$=|]", word) \
                or "://" in word:
            continue
        parts = word.split("/")
        if any(p.startswith(".") and p != ".claude" for p in parts[:-1]):
            continue  # hidden directories are made at run time
        if len(parts) > 1:
            # a file by its suffix or a directory by its trailing slash
            # (a counter such as data/h2d_mb is neither)
            if (word.endswith(SUFFIXES) or word.endswith("/")) and any(
                    os.path.isdir(os.path.join(ROOT, base, parts[0]))
                    for base in BASES):
                yield word
        elif word.endswith(SUFFIXES):
            yield word


def test_the_reader_finds_paths():
    found = set(cited_paths(
        "see `scripts/x.py:12`, `python train.py --config a`, `PERF.md`, "
        "`tests/test_a.py::TestB::test_c`, `trainers/base.py:_f`, "
        "`imaginaire/ref.py`, `<logdir>/x.json`, `a.b.c`, `benchmark/.cache/x`, "
        "`data/h2d_mb`, `imaginaire_tpu/ops/`"))
    assert found == {"scripts/x.py", "train.py", "PERF.md", "tests/test_a.py",
                     "trainers/base.py", "imaginaire_tpu/ops/"}


@pytest.mark.parametrize("name", list(DOCUMENTS))
def test_cited_paths_exist(name):
    cited = sorted(set(cited_paths(DOCUMENTS[name]())))
    assert cited, f"{name} cites no path: the reader is broken"
    sources, basenames = _program_sources(), _tracked_basenames()
    missing = []
    for path in cited:
        if "/" in path:
            ok = any(glob.glob(os.path.join(ROOT, base, path))
                     for base in BASES)
        else:
            ok = (path in basenames or bool(glob.glob(os.path.join(ROOT, path)))
                  or path.lstrip("*") in sources)
        if not ok:
            missing.append(path)
    assert not missing, f"{name} cites files that do not exist: {missing}"
