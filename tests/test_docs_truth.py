"""The documents name files that exist.

One case per document (``README.md`` and each ``docs/*.md``): every
back-quoted repo path in it is in the tree.  One more case over the
comments and docstrings of ``elasticdl_tpu/**/*.py``: every ``docs/``,
``artifacts/`` or ``tools/`` path they cite is in the tree.  ROADMAP.md,
PERF.md and CHANGES.md are histories and name deleted files on purpose;
``benchmark/`` keeps its own comments.
"""

import functools
import glob
import io
import os
import re
import tokenize

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

_DIRS = ("tools", "elasticdl_tpu", "benchmark", "artifacts", "tests", "docs")


def _under(dirs) -> re.Pattern:
    """A path under one of ``dirs``, wherever it stands in the text."""
    return re.compile(rf"(?<![\w./-])(?:{'|'.join(dirs)})/[\w./*-]+")


_UNDER = _under(_DIRS)
_CITED_IN_CODE = _under(("docs", "artifacts", "tools"))
#: a bare file name: the whole first word of a back-quoted span
_BARE = re.compile(r"^[\w-]+\.(?:py|json|jsonl|md|toml)\b")
_SPAN = re.compile(r"`([^`\n]+)`")

#: files a job writes where it runs; no checkout holds them
RUNTIME_OUTPUTS = {
    "metrics.jsonl", "job_progress.json", "checkpoint_manifest.json", "pod_registry.json",
}


@functools.cache
def _basenames() -> frozenset:
    names = {n for n in os.listdir(REPO) if os.path.isfile(os.path.join(REPO, n))}
    for d in _DIRS:
        for _, _, files in os.walk(os.path.join(REPO, d)):
            names.update(files)
    return frozenset(names)


def _exists(path: str) -> bool:
    """``a/b.py``, a glob with a match, a directory, or a dotted name
    inside a module (``tools/artifact.latency_stats``); a bare file name
    is any file of the tree (``trace.py`` for ``common/trace.py``)."""
    path = path.rstrip(".:,")
    if "/" not in path:
        return path in _basenames() or path in RUNTIME_OUTPUTS
    if "*" in path:
        return bool(glob.glob(os.path.join(REPO, path)))
    module = path.rsplit(".", 1)[0] + ".py"
    return any(os.path.exists(os.path.join(REPO, p)) for p in (path, path + ".py", module))


def _doc_paths(text: str):
    for span in _SPAN.findall(text):
        yield from _UNDER.findall(span)
        bare = _BARE.match(span)
        if bare:
            yield bare.group(0)


def _comment_text(path: str) -> str:
    """Comments and string statements (docstrings) of one source file."""
    with open(path, "rb") as f:
        source = f.read()
    out, prev = [], tokenize.NEWLINE
    for tok in tokenize.tokenize(io.BytesIO(source).readline):
        if tok.type == tokenize.COMMENT:
            out.append(tok.string)
        elif tok.type == tokenize.STRING and prev in (
            tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT, tokenize.ENCODING,
        ):
            out.append(tok.string)
        if tok.type not in (tokenize.COMMENT, tokenize.NL):
            prev = tok.type
    return "\n".join(out)


def _missing_in_doc(doc: str):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        return sorted({p for p in _doc_paths(f.read()) if not _exists(p)})


def _missing_in_comments(package: str):
    missing = set()
    for path in glob.glob(os.path.join(REPO, package, "**", "*.py"), recursive=True):
        for p in _CITED_IN_CODE.findall(_comment_text(path)):
            if not _exists(p):
                missing.add(f"{os.path.relpath(path, REPO)}: {p}")
    return sorted(missing)


@pytest.mark.parametrize("target", DOCS + ["elasticdl_tpu"])
def test_cited_paths_exist(target):
    check = _missing_in_doc if target.endswith(".md") else _missing_in_comments
    assert check(target) == []
