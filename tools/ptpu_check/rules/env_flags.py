"""env-flag-drift — every ``PTPU_*`` flag read in code must be in the
README, and every README flag must still exist in code.  Both
directions.

The bug class: the flag surface grew one env var per PR
(``PTPU_MONITOR``, ``PTPU_TRACE``, ``PTPU_FAULTS``, ``PTPU_MEMOBS``,
...) and the README's documented set drifted behind the code's read
set — an operator tuning a fleet cannot discover half the knobs, and a
documented knob that silently stopped being read is worse (set it,
believe it, get nothing).  The multi-process era (fleet env plumbing,
per-rank ``PTPU_REPLICA_ID``) multiplies the surface.

Mechanics: flag READS/WRITES are collected from ``os.environ.get /
os.getenv / environ[...] / environ.setdefault / environ.pop`` call
sites whose key is a full ``PTPU_*`` string literal.  The documented
set is every ``PTPU_*`` token in the repo-root ``README.md``.  For the
README→code direction, root-level driver scripts outside the analyzer's
default scope (``bench.py`` etc.) and ``examples/`` are included via a
light text scan, so a flag read only there does not get flagged as
phantom.

- code→README: an undocumented flag is flagged AT ITS READ SITE (fix:
  document it in the README "Environment flags" table, or suppress with
  ``# ptpu-check[env-flag-drift]: why`` for genuinely-internal debug
  knobs);
- README→code: a documented flag with no read anywhere is flagged with
  ``path=README.md`` at its first mention line (fix: delete the doc row
  or restore the reader — there is no inline suppression in markdown;
  a deliberately-documented-ahead flag belongs in the baseline).

No README.md at the repo root → the rule is silent (fixture runs).
"""
from __future__ import annotations

import ast
import os
import re

from ..callgraph import dotted_name
from ..core import Finding, Rule

FLAG_RE = re.compile(r"PTPU_[A-Z0-9]+(?:_[A-Z0-9]+)*")
ENV_CALL_LASTS = {"get", "getenv", "setdefault", "pop"}
# root-level .py files + examples/ are outside the analyzer's default
# scope but still read flags (bench.py's PTPU_BENCH_HISTORY), and shell
# CI lanes read flags too (run_ci.sh's PTPU_CHECK_BASE); scan them
# textually for the README→code direction only
EXTRA_SCAN_DIRS = ("", "examples", "tools", "scripts")
EXTRA_SCAN_EXTS = (".py", ".sh")


def _env_flag_sites(ctx):
    """[(flag, node)] for every PTPU_* literal used as an environ key."""
    out = []
    for node in ast.walk(ctx.tree):
        key = None
        if isinstance(node, ast.Call):
            dn = dotted_name(node.func) or ""
            last = dn.rsplit(".", 1)[-1]
            is_env = ("environ" in dn and last in ENV_CALL_LASTS) \
                or last == "getenv"
            if is_env and node.args:
                key = node.args[0]
        elif isinstance(node, ast.Subscript):
            dn = dotted_name(node.value) or ""
            if dn.endswith("environ"):
                key = node.slice
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            m = FLAG_RE.fullmatch(key.value)
            if m:
                out.append((key.value, node))
    return out


def _readme(project):
    """(lines list, {flag: first line no}) from the repo-root README, or
    (None, {}) when absent.  Cached on the project."""
    cached = getattr(project, "_env_readme", None)
    if cached is not None:
        return cached
    lines, flags = None, {}
    root = getattr(project, "repo_root", None)
    path = os.path.join(root, "README.md") if root else None
    if path and os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        for i, ln in enumerate(lines, start=1):
            for m in FLAG_RE.finditer(ln):
                flags.setdefault(m.group(0), i)
    project._env_readme = (lines, flags)
    return project._env_readme


def _code_flags(project):
    """All flags used anywhere in code: analyzed contexts' env sites
    plus the light out-of-scope text scan.  Cached on the project."""
    cached = getattr(project, "_env_code_flags", None)
    if cached is not None:
        return cached
    used = set()
    for ctx in project.contexts:
        if ctx.tree is None:
            continue
        for flag, _ in _env_flag_sites(ctx):
            used.add(flag)
    root = getattr(project, "repo_root", None)
    if root:
        for sub in EXTRA_SCAN_DIRS:
            d = os.path.join(root, sub)
            if not os.path.isdir(d):
                continue
            for name in sorted(os.listdir(d)):
                if not name.endswith(EXTRA_SCAN_EXTS):
                    continue
                try:
                    with open(os.path.join(d, name),
                              encoding="utf-8") as f:
                        used.update(FLAG_RE.findall(f.read()))
                except OSError:
                    continue
    project._env_code_flags = used
    return used


class EnvFlagDriftRule(Rule):
    id = "env-flag-drift"
    doc = ("every PTPU_* env flag read in code is documented in README "
           "and every documented flag is still read — both directions")
    descends_from = ("PRs 1-13 each added env knobs (PTPU_MONITOR, "
                     "PTPU_TRACE, PTPU_FAULTS, ...); 20+ reads had "
                     "drifted out of the README's documented set by "
                     "PR 11 — undiscoverable fleet tuning knobs")

    def check(self, ctx, project):
        readme_lines, readme_flags = _readme(project)
        if readme_lines is None:
            return
        # code -> README: flag each undocumented read site (first site
        # per flag per file keeps the noise proportional to flags, not
        # call sites)
        seen_here = set()
        for flag, node in _env_flag_sites(ctx):
            if flag in readme_flags or flag in seen_here:
                continue
            seen_here.add(flag)
            if not ctx.suppressed(self.id, node.lineno):
                yield self.finding(
                    ctx, node,
                    f"`{flag}` is read here but documented nowhere in "
                    f"README.md — add it to the \"Environment flags\" "
                    f"table (operators cannot discover undocumented "
                    f"knobs)")
        # README -> code: emitted once, from the lexicographically first
        # analyzed context so the report stays deterministic and
        # single-copy.  Only meaningful when the analyzed set actually
        # covers the tree — a partial-path run (`ptpu_check one.py`)
        # cannot see the readers and every documented flag would look
        # phantom; gate on the package root being in scope.
        if "paddle_tpu/__init__.py" not in project.by_rel:
            return
        if project.contexts and ctx is project.contexts[0]:
            used = _code_flags(project)
            for flag, line in sorted(readme_flags.items()):
                if flag not in used:
                    yield Finding(
                        self.id, "README.md", line, 0,
                        f"`{flag}` is documented but read nowhere in "
                        f"code — a knob operators can set with no "
                        f"effect; delete the row or restore the reader")
