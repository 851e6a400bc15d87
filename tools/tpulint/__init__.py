"""tpulint — framework-aware static analysis for mxnet_tpu.

Generic linters know Python; they do not know that in THIS codebase a
``functools.lru_cache`` holding a ``jax.jit`` executable is a silent-
recompile bug, that a donation the hlolint audit cannot see is how
"it silently stopped aliasing" survives review, or that a module that parses env
vars at import breaks the "gates cost one attribute read when off"
discipline every perf PR has leaned on since PR 7. Those rules lived in
reviewer memory; tpulint turns them into a blocking CI gate
(``ci/run.sh``: ``python -m tools.tpulint mxnet_tpu tools --strict``).

Rules (see :mod:`tools.tpulint.rules` for the exact semantics, and
``docs/faq/perf.md`` "Machine-checked invariants" for the why):

* ``executable-cache``    — compiled executables live in named
  :class:`~mxnet_tpu.compile_cache.CompileCache`\\ s, never
  ``lru_cache``/dict memos.
* ``cache-memory-tracking`` — big bounded caches pass
  ``track_memory=False``.
* ``gate-discipline``     — no import-time side effects (thread starts,
  raw env parsing, device touches) outside the lazy gate helpers.
* ``tracer-hygiene``      — no wall-clock / np.random / env reads
  lexically inside functions handed to ``jax.jit`` & friends.
* ``env-var-registry``    — every ``MXNET_*`` knob read in code has a row
  in ``docs/faq/env_var.md`` and vice versa.

Escape hatch: ``# tpulint: disable=<rule> (reason)`` on the offending
line (or the ``def``/decorator line for function-level findings). The
reason is REQUIRED — a bare disable is itself a finding
(``bad-disable``), because an unexplained suppression is how folklore
got lost in the first place.

The runtime complement — the MXNET_DEBUG_SYNC lock-order recorder — is
:mod:`mxnet_tpu.analysis`; CI runs both halves.
"""
from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field

__all__ = ["Finding", "SourceFile", "lint_paths", "lint_sources",
           "collect_files", "RULES"]

_DISABLE_RE = re.compile(
    r"#\s*tpulint:\s*disable=([A-Za-z0-9_,-]+)\s*(?:\((.*?)\))?")


@dataclass
class Finding:
    """One rule violation: ``path:line: rule: message``."""

    path: str
    line: int
    rule: str
    message: str
    # additional lines whose disable comment also suppresses this finding
    # (the def line and decorator lines for function-level rules)
    alt_lines: tuple = field(default_factory=tuple, repr=False)

    def __str__(self):
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"


class SourceFile:
    """A parsed module: AST + per-line ``tpulint: disable`` map."""

    def __init__(self, path, text=None):
        self.path = path
        self.text = open(path, encoding="utf-8").read() if text is None \
            else text
        self.tree = ast.parse(self.text, filename=path)
        # line -> set of disabled rule names; bad disables (no reason)
        self.disables = {}
        self.bad_disables = []      # (line, rules) with missing reason
        self._scan_comments()

    def _scan_comments(self):
        try:
            toks = tokenize.generate_tokens(
                io.StringIO(self.text).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _DISABLE_RE.search(tok.string)
                if not m:
                    continue
                rules = {r.strip() for r in m.group(1).split(",")
                         if r.strip()}
                reason = (m.group(2) or "").strip()
                line = tok.start[0]
                if not reason:
                    self.bad_disables.append((line, sorted(rules)))
                    continue        # a reasonless disable suppresses nothing
                self.disables.setdefault(line, set()).update(rules)
                # a STANDALONE disable comment (nothing but whitespace
                # before it) also covers the following line, so long
                # statements can carry the annotation above them
                if not tok.line[:tok.start[1]].strip():
                    self.disables.setdefault(line + 1, set()).update(rules)
        except tokenize.TokenError:  # pragma: no cover — ast.parse passed
            pass

    def disabled(self, rule, *lines):
        return any(rule in self.disables.get(ln, ()) for ln in lines)


def collect_files(paths):
    """Expand files/dirs into a sorted ``.py`` file list (dirs walked
    recursively; __pycache__ skipped)."""
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
        else:
            raise FileNotFoundError(p)
    return sorted(set(out))


def lint_sources(sources, env_doc=None, select=None):
    """Lint already-constructed :class:`SourceFile`\\ s. ``select`` limits
    to those rule names; ``env_doc`` is the path of the env-var doc table
    (None skips the env-var-registry rule). Returns findings sorted by
    (path, line)."""
    from . import rules

    findings = []
    active = {name: fn for name, fn in RULES.items()
              if select is None or name in select}
    for sf in sources:
        for line, bad in sf.bad_disables:
            findings.append(Finding(
                sf.path, line, "bad-disable",
                f"tpulint disable of {','.join(bad)} without a "
                f"'(reason)' — explain why or fix the finding"))
        for name, fn in active.items():
            for f in fn(sf):
                if not sf.disabled(name, f.line, *f.alt_lines):
                    findings.append(f)
    if env_doc is not None and (select is None
                                or "env-var-registry" in select):
        findings.extend(rules.check_env_registry(sources, env_doc))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def lint_paths(paths, env_doc=None, select=None):
    """Parse + lint ``paths`` (files or directories). Unparseable files
    become findings, not crashes."""
    sources, findings = [], []
    for path in collect_files(paths):
        try:
            sources.append(SourceFile(path))
        except SyntaxError as e:
            findings.append(Finding(path, e.lineno or 1, "parse-error",
                                    f"could not parse: {e.msg}"))
    findings.extend(lint_sources(sources, env_doc=env_doc, select=select))
    return findings


# populated by rules.py at import (name -> checker(sf) -> [Finding])
RULES = {}

from . import rules as _rules  # noqa: E402,F401 — registers RULES
