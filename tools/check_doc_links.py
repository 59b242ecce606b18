#!/usr/bin/env python
"""Fail on broken intra-repo links and stale env-knob names in the docs.

Scans every ``*.md`` under the repo root (skipping dot-dirs and
``experiments/``) for inline links/images ``[text](target)`` and verifies
each *relative* target resolves to an existing file or directory.  External
schemes (http/https/mailto) and pure ``#anchor`` links are ignored; a
``path#anchor`` target is checked for the path part only.

Additionally, every ``REPRO_*`` environment knob the Markdown docs mention
must correspond to a string literal in the Python tree (``src/``,
``benchmarks/``, ``tools/`` -- i.e. a grep-able ``os.environ`` read) -- a
documented knob nobody reads is exactly the kind of rot this check exists
for.  A knob the code stopped reading is listed in
``docs/retired-knobs.md``: older text (change logs, task statements) may
still name it, and the check fails if code reads one again.

CI runs this in the docs job so README/docs can't rot silently:

    python tools/check_doc_links.py
"""

from __future__ import annotations

import os
import re
import sys

# inline [text](target) / ![alt](target); stops at the first ')' so code
# spans with parens don't confuse it
_LINK_RE = re.compile(r"!?\[[^\]]*\]\(([^)\s]+)\)")
_SKIP_SCHEMES = ("http://", "https://", "mailto:", "ftp://")
_SKIP_DIRS = {"experiments", "node_modules", "__pycache__"}


def iter_markdown(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if not d.startswith(".") and d not in _SKIP_DIRS]
        for name in filenames:
            if name.endswith(".md"):
                yield os.path.join(dirpath, name)


def check_file(path: str, root: str):
    """-> (broken [(relpath, lineno, target)], n_intra_repo_links_checked)."""
    broken, n_links = [], 0
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            for m in _LINK_RE.finditer(line):
                target = m.group(1)
                if target.startswith(_SKIP_SCHEMES) or target.startswith("#"):
                    continue
                rel = target.split("#", 1)[0]
                if not rel:
                    continue
                n_links += 1
                base = root if rel.startswith("/") else os.path.dirname(path)
                resolved = os.path.normpath(
                    os.path.join(base, rel.lstrip("/")))
                if not os.path.exists(resolved):
                    broken.append((os.path.relpath(path, root), lineno,
                                   target))
    return broken, n_links


_KNOB_RE = re.compile(r"\bREPRO_[A-Z0-9_]+\b")
_CODE_DIRS = ("src", "benchmarks", "tools")
RETIRED_KNOBS = os.path.join("docs", "retired-knobs.md")


def knobs_in_code(root: str) -> set:
    """Every REPRO_* string literal in the Python tree (the set of knobs
    some ``os.environ`` read actually consults)."""
    found = set()
    for sub in _CODE_DIRS:
        top = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for name in filenames:
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, name),
                          encoding="utf-8") as f:
                    found.update(_KNOB_RE.findall(f.read()))
    return found


def retired_knobs(root: str) -> set:
    """The knobs ``docs/retired-knobs.md`` lists (empty without it)."""
    path = os.path.join(root, RETIRED_KNOBS)
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        return set(_KNOB_RE.findall(f.read()))


def check_env_knobs(root: str):
    """-> (stale [(relpath, lineno, knob)], n_knob_mentions_checked,
    revived [knob]: retired knobs the code reads again)."""
    known = knobs_in_code(root)
    retired = retired_knobs(root)
    stale, n_mentions = [], 0
    for md in iter_markdown(root):
        with open(md, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                for knob in _KNOB_RE.findall(line):
                    n_mentions += 1
                    if knob not in known and knob not in retired:
                        stale.append((os.path.relpath(md, root), lineno,
                                      knob))
    return stale, n_mentions, sorted(retired & known)


def main(argv=None) -> int:
    root = os.path.abspath(
        (argv or sys.argv[1:] or [os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "..")])[0])
    broken, n_files, n_links = [], 0, 0
    for md in iter_markdown(root):
        n_files += 1
        file_broken, file_links = check_file(md, root)
        broken.extend(file_broken)
        n_links += file_links
    for path, lineno, target in broken:
        print(f"BROKEN {path}:{lineno}: {target}")
    stale, n_knobs, revived = check_env_knobs(root)
    for path, lineno, knob in stale:
        print(f"STALE-KNOB {path}:{lineno}: {knob} is documented but no "
              f"code under {'/'.join(_CODE_DIRS)} reads it")
    for knob in revived:
        print(f"RETIRED-KNOB {knob} is listed in {RETIRED_KNOBS} but code "
              f"under {'/'.join(_CODE_DIRS)} reads it")
    print(f"# checked {n_files} markdown files, {n_links} intra-repo links "
          f"({len(broken)} broken), {n_knobs} env-knob mentions "
          f"({len(stale)} stale)")
    return 1 if broken or stale or revived else 0


if __name__ == "__main__":
    sys.exit(main())
