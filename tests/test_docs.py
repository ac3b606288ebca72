"""The documents name files that exist.

`README.md`, `PERF.md` and `ROADMAP.md` are what a new owner reads first;
a sentence that names a script or a record that is gone sends them to a
second, stale account of the system. Every backticked name that ends in
`.py`, `.json`, `.jsonl` or `.md` must resolve: as a path from the root,
from `cake_tpu/` or from `benchmark/`, or, for a bare basename, as the
basename of some tracked file. A name is exempt only in a paragraph or
list item that itself says it was deleted.

`PERF.md` is read whole by every session before it plans or builds, so its
size is held too, in bytes and in the length of a line: a limit on the
number of lines alone was met for twenty PRs by not breaking them."""

import functools
import os
import re
import subprocess
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parent.parent
_SUFFIXES = (".py", ".json", ".jsonl", ".md")

# what a run writes or reads beside the checkout, what the driver puts
# there and takes away, and the guides outside it
_ALLOWED = {
    "config.json", "tokenizer.json",
    "ISSUE.md", "REVIEW.md",
    # files of the guides under /opt/skills/guides
    "SKILL.md", "workloads.md", "architectures.jsonl", "pallas_guide.md",
    "kinds/inference-serving.md",
}
_ALLOWED_PATTERNS = (
    r"^(.*\.)?trace\.json$",  # --trace outputs
    r"^/opt/skills/",         # the guides
    r"^/root/",               # the driver's files around the checkout
)
_SKIP_DIRS = {"__pycache__", "chiprun_out"}


@functools.lru_cache(maxsize=None)
def _basenames() -> frozenset:
    """basenames of the files git tracks or would add: an ignored artifact
    of an earlier run makes no dead name resolve; without a .git (an
    unpacked archive holds tracked files only) the tree is walked"""
    try:
        listed = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=_ROOT, capture_output=True, text=True, check=True,
        ).stdout.splitlines()
        if listed:
            # a path the index still holds but the tree has lost is gone
            return frozenset(os.path.basename(f) for f in listed
                             if (_ROOT / f).exists())
    except (OSError, subprocess.CalledProcessError):
        pass
    names = set()
    for _, dirs, files in os.walk(_ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in _SKIP_DIRS]
        names.update(files)
    return frozenset(names)


def _blocks(text: str):
    """(first line number, lines) of each paragraph or list item: a blank
    line ends a block and a list marker starts one"""
    start, block = 0, []
    for n, line in enumerate(text.splitlines(), 1):
        if block and (not line.strip()
                      or re.match(r"\s*([-*]|\d+\.)\s", line)):
            yield start, block
            block = []
        if line.strip():
            if not block:
                start = n
            block.append(line)
    if block:
        yield start, block


def _names(line: str):
    """the file names inside a line's backtick spans, `:line` stripped"""
    for span in re.findall(r"`([^`]+)`", line):
        for token in span.split():
            token = re.sub(r":[0-9][0-9,:-]*$", "", token.strip("()[],;'\""))
            if (token.endswith(_SUFFIXES) and token not in _SUFFIXES
                    and not re.search(r"[<>*{}$]|\.\.\.", token)):
                yield token


def _resolves(name: str, basenames: frozenset) -> bool:
    if name in _ALLOWED or any(re.match(p, name) for p in _ALLOWED_PATTERNS):
        return True
    if "/" not in name:
        return name in basenames
    return any((base / name).exists()
               for base in (_ROOT, _ROOT / "cake_tpu", _ROOT / "benchmark"))


@pytest.mark.parametrize("doc", ["README.md", "PERF.md", "ROADMAP.md"])
def test_document_names_files_that_exist(doc):
    basenames = _basenames()
    missing = []
    for start, block in _blocks((_ROOT / doc).read_text()):
        if any("deleted" in line for line in block):
            continue
        missing += [f"{doc}:{n}: {name}"
                    for n, line in enumerate(block, start)
                    for name in _names(line)
                    if not _resolves(name, basenames)]
    assert not missing, "\n".join(missing)


# PERF.md's room: the ledger keeps the numbers and CHANGES.md each PR's
# full account; what outgrows this is merged into what it taught
_PERF_MAX_BYTES = 100_000
_PERF_MAX_LINE = 2_000


def test_perf_md_is_under_its_size():
    size = len((_ROOT / "PERF.md").read_bytes())
    assert size < _PERF_MAX_BYTES, (
        f"PERF.md is {size} bytes: merge its oldest findings into what "
        f"they taught (under {_PERF_MAX_BYTES})")


def test_perf_md_has_no_line_over_its_length():
    long = [f"PERF.md:{n}: {len(line)} characters"
            for n, line in enumerate(
                (_ROOT / "PERF.md").read_text().splitlines(), 1)
            if len(line) > _PERF_MAX_LINE]
    assert not long, "\n".join(long)
