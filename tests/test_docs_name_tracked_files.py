"""The documents name files the checkout has: a deleted runner or record
must leave no link and no command behind (ISSUE 33)."""

import pathlib
import posixpath
import re
import subprocess

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

LINK = re.compile(r"\]\(([^)\s]+)\)")
COMMAND = re.compile(r"\bpython3? ([\w./-]+\.py)\b")
SCHEME = re.compile(r"[a-zA-Z][a-zA-Z0-9+.-]*:")


def _tracked():
    try:
        listed = subprocess.run(
            ["git", "ls-files"], cwd=REPO, check=True, capture_output=True,
            text=True, timeout=60).stdout.splitlines()
    except (OSError, subprocess.SubprocessError):
        listed = []
    if listed:
        return set(listed)
    # an exported tree (`git archive`) holds the tracked files and no others
    return {p.relative_to(REPO).as_posix() for p in REPO.rglob("*")
            if p.is_file()}


@pytest.mark.parametrize("doc", [
    "README.md", "docs/ARCHITECTURE.md", "docs/BENCHMARKS.md",
    ".claude/skills/verify/SKILL.md"])
def test_links_and_commands_name_tracked_files(doc):
    tracked = _tracked()
    assert doc in tracked
    text = (REPO / doc).read_text()
    here = posixpath.dirname(doc)
    named = {}
    for target in LINK.findall(text):
        path = target.split("#")[0]
        if SCHEME.match(target) or not path:
            continue   # another site, or a heading of this document
        named[f"]({target})"] = posixpath.normpath(posixpath.join(here, path))
    for script in COMMAND.findall(text):
        named[f"python {script}"] = posixpath.normpath(script)  # run from the root
    missing = {said: path for said, path in named.items()
               if path not in tracked
               and not any(t.startswith(path + "/") for t in tracked)}
    assert not missing, f"{doc} names what the checkout has not: {missing}"
