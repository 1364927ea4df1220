"""flag-doc: every knob the code reads is documented, and vice versa.

The README's flag tables are the operational contract: a
`GETHSHARDING_*` env var or `--flag` that exists only in code is a knob
nobody can discover, and a documented one that no code reads is a doc
that lies. Both directions rot silently; this rule diffs them
mechanically.

Code side:
- env vars: every string literal (and f-string skeleton) shaped
  `GETHSHARDING_[A-Z0-9_]*` anywhere in the package, chip_smoke.py,
  scripts/ and benchmark/ — call args, dict keys, comparisons — EXCEPT docstrings.
  Dynamic names (`f"GETHSHARDING_CLASS_{op}"`) become skeletons with
  `*` at the formatted holes.
- CLI flags: `add_argument("--…")` literals. Flags of the package CLIs
  (gethsharding_tpu/**) must be documented; the other files' flags only
  feed the stale-doc direction (internal tools may keep private knobs).

Doc side (README.md): `GETHSHARDING_…` tokens anywhere (placeholders
like `<NAME>` become skeleton holes), `--flag`-shaped tokens anywhere.

Checks:
- code env var with no README mention        -> undocumented-env
- README env var no code reads               -> stale-env-doc
- package CLI flag with no README mention    -> undocumented-flag
- README `--flag` no parser defines          -> stale-flag-doc
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Set, Tuple

from gethsharding_tpu.analysis.core import Corpus, Finding, rule

RULE = "flag-doc"
DOC_FILES = ("README.md",)

_ENV_RE = re.compile(r"^GETHSHARDING_[A-Z0-9_]*$")
_DOC_ENV_RE = re.compile(r"GETHSHARDING(?:_(?:[A-Z0-9]+|<[A-Za-z_]+>))+_?")
_DOC_FLAG_RE = re.compile(r"--[a-z0-9][a-z0-9-]*")


def _skeleton_to_regex(skel: str) -> "re.Pattern[str]":
    parts = [re.escape(p) for p in skel.split("*")]
    return re.compile("^" + "[A-Z0-9_]+".join(parts) + "$")


def _code_env_tokens(corpus: Corpus) -> Dict[str, Tuple[str, int]]:
    """token/skeleton -> first (rel, line). Skeletons contain '*'."""
    out: Dict[str, Tuple[str, int]] = {}
    for sf in list(corpus.files) + list(corpus.extra_files):
        if sf.tree is None:
            continue
        skip = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                body = getattr(node, "body", [])
                if body and isinstance(body[0], ast.Expr) and \
                        isinstance(body[0].value, ast.Constant):
                    skip.add(id(body[0].value))  # docstring
            elif isinstance(node, ast.JoinedStr):
                for v in node.values:  # pieces count via the skeleton
                    skip.add(id(v))
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and id(node) not in skip:
                token = node.value
                if _ENV_RE.match(token):
                    if token.endswith("_"):
                        token += "*"  # concatenation prefix
                    out.setdefault(token, (sf.rel, node.lineno))
            elif isinstance(node, ast.JoinedStr):
                parts = []
                for v in node.values:
                    if isinstance(v, ast.Constant) and \
                            isinstance(v.value, str):
                        parts.append(v.value)
                    else:
                        parts.append("*")
                skel = "".join(parts)
                if skel.startswith("GETHSHARDING_") and \
                        _ENV_RE.match(skel.replace("*", "X")):
                    out.setdefault(skel, (sf.rel, node.lineno))
    return out


def _code_flag_tokens(corpus: Corpus, package_only: bool) -> \
        Dict[str, Tuple[str, int]]:
    out: Dict[str, Tuple[str, int]] = {}
    files = list(corpus.files) if package_only else \
        list(corpus.files) + list(corpus.extra_files)
    for sf in files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "add_argument":
                for arg in node.args:
                    if isinstance(arg, ast.Constant) and \
                            isinstance(arg.value, str) and \
                            arg.value.startswith("--"):
                        out.setdefault(arg.value, (sf.rel, node.lineno))
    return out


def _doc_tokens(corpus: Corpus):
    env: Set[str] = set()
    flags: Set[str] = set()
    for rel in DOC_FILES:
        text = corpus.read_doc(rel)
        if text is None:
            continue
        for tok in _DOC_ENV_RE.findall(text):
            tok = tok.rstrip("_") if tok.endswith("_") and \
                not tok.endswith("__") else tok
            env.add(re.sub(r"<[A-Za-z_]+>", "*", tok))
        # EVERY `--flag`-shaped token anywhere in the doc counts — the
        # shape doesn't occur in prose, and tying this to backtick
        # pairing breaks on fenced code blocks (3-backtick fences flip
        # span parity) and on multi-flag spans
        flags.update(_DOC_FLAG_RE.findall(text))
    return env, flags


def _env_documented(token: str, doc_env: Set[str]) -> bool:
    if token in doc_env:
        return True
    literals = [d for d in doc_env if "*" not in d]
    skeletons = [d for d in doc_env if "*" in d]
    if "*" in token:
        # a skeleton is documented if the doc has the same skeleton or
        # a literal instance of it (the autotune prefix case)
        pat = _skeleton_to_regex(token)
        return any(pat.match(lit) for lit in literals)
    return any(_skeleton_to_regex(skel).match(token) for skel in skeletons)


def _env_exists(token: str, code_env: Dict[str, Tuple[str, int]]) -> bool:
    if token in code_env:
        return True
    code_literals = [c for c in code_env if "*" not in c]
    code_skels = [c for c in code_env if "*" in c]
    if "*" in token:
        pat = _skeleton_to_regex(token)
        return any(pat.match(lit) for lit in code_literals) or \
            any(_skeleton_to_regex(c).pattern == pat.pattern
                for c in code_skels)
    return any(_skeleton_to_regex(c).match(token) for c in code_skels)


@rule(RULE, "GETHSHARDING_* env vars and CLI --flags are documented in "
            "the README flag tables, and the tables don't go stale")
def check(corpus: Corpus) -> List[Finding]:
    findings: List[Finding] = []
    doc_env, doc_flags = _doc_tokens(corpus)
    code_env = _code_env_tokens(corpus)
    pkg_flags = _code_flag_tokens(corpus, package_only=True)
    all_flags = _code_flag_tokens(corpus, package_only=False)

    for token, (rel, line) in sorted(code_env.items()):
        if not _env_documented(token, doc_env):
            findings.append(Finding(
                RULE, rel, line,
                f"env var `{token.replace('*', '<...>')}` is read here but "
                f"appears nowhere in {' / '.join(DOC_FILES)}",
                f"undocumented-env:{token}"))
    for token in sorted(doc_env):
        if not _env_exists(token, code_env):
            findings.append(Finding(
                RULE, DOC_FILES[0], 0,
                f"documented env var `{token.replace('*', '<...>')}` is "
                f"read by no code — stale doc",
                f"stale-env-doc:{token}"))
    for flag, (rel, line) in sorted(pkg_flags.items()):
        if flag not in doc_flags:
            findings.append(Finding(
                RULE, rel, line,
                f"CLI flag `{flag}` is defined here but appears in no "
                f"mention in {' / '.join(DOC_FILES)}",
                f"undocumented-flag:{flag}"))
    for flag in sorted(doc_flags):
        if flag not in all_flags:
            findings.append(Finding(
                RULE, DOC_FILES[0], 0,
                f"documented CLI flag `{flag}` is defined by no parser — "
                f"stale doc",
                f"stale-flag-doc:{flag}"))
    return findings
