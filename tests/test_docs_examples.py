"""Docs cannot rot: every fenced ```python block in docs/*.md and README.md
must execute, and every relative link / repo path a doc mentions must
exist.  (The CI `docs` job runs exactly this file.)"""
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = sorted((REPO / "docs").glob("*.md"))
ALL_MD = DOCS + [REPO / "README.md"]

FENCE = re.compile(r"^```(\w*)\s*$")


def fenced_blocks(path, lang="python"):
    """(start_line, code) for every fenced block tagged ``lang``."""
    out, cur, cur_start, in_lang = [], [], 0, False
    for i, line in enumerate(path.read_text().splitlines(), 1):
        m = FENCE.match(line)
        if m:
            if in_lang:
                out.append((cur_start, "\n".join(cur)))
                cur, in_lang = [], False
            elif m.group(1) == lang:
                in_lang, cur_start = True, i + 1
            continue
        if in_lang:
            cur.append(line)
    return out


def all_python_examples():
    return [pytest.param(path, start, code,
                         id=f"{path.name}:{start}")
            for path in ALL_MD
            for start, code in fenced_blocks(path)]


def test_docs_tree_complete():
    names = {p.name for p in DOCS}
    assert {"architecture.md", "wire-format.md", "policies.md",
            "metrics.md"} <= names


@pytest.mark.parametrize("path,start,code", all_python_examples())
def test_python_example_executes(path, start, code):
    """Each example is a self-contained script (PYTHONPATH=src assumed,
    as everywhere in this repo)."""
    try:
        exec(compile(code, f"{path.name}:{start}", "exec"), {})
    except Exception as e:   # pragma: no cover - failure formatting
        pytest.fail(f"{path.name} example at line {start} failed: {e!r}")


LINK = re.compile(r"\[[^\]]*\]\(([^)#]+)(?:#[^)]*)?\)")
REPO_PATH = re.compile(r"`((?:src|docs|tests|examples)/[\w./-]+)`")


@pytest.mark.parametrize("path", ALL_MD, ids=lambda p: p.name)
def test_relative_links_resolve(path):
    text = path.read_text()
    broken = []
    for target in LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        if not (path.parent / target).exists():
            broken.append(target)
    assert not broken, f"{path.name}: dead link(s) {broken}"


@pytest.mark.parametrize("path", ALL_MD, ids=lambda p: p.name)
def test_mentioned_repo_paths_exist(path):
    missing = [m for m in REPO_PATH.findall(path.read_text())
               if not (REPO / m).exists()]
    assert not missing, f"{path.name}: nonexistent path(s) {missing}"


def test_readme_flags_match_drivers():
    """Flags the README advertises must exist in the drivers (drift guard:
    every --flag in a README bash block naming train.py/serve.py)."""
    from repro.launch.train import build_parser
    readme = (REPO / "README.md").read_text()
    # the parser itself: it also holds generated flags (--no-reduced)
    train_flags = set(build_parser()._option_string_actions)
    serve_src = (REPO / "examples/serve.py").read_text()
    for _, block in fenced_blocks(REPO / "README.md", lang="bash"):
        for cmd in re.split(r"\n(?=\S)", block):
            flags = re.findall(r"(--[\w-]+)", cmd)
            if "repro.launch.train" in cmd:
                missing = [f for f in flags if f not in train_flags]
            elif "serve.py" in cmd:
                missing = [f for f in flags if f'"{f}"' not in serve_src]
            else:
                continue
            assert not missing, f"README advertises {missing} not in driver"
    assert readme.count("docs/") >= 4       # the pointers exist
