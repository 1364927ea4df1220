"""shardlint core: corpus loading, rule registry, findings, baseline.

The geth lineage wires `go vet` + the race detector into its build; this
package is the TPU rewrite's analogue — an AST-level pass with
repo-specific rules (jit-purity, host-sync, lock-order, race-guard,
layering, backend-contract, thread-lifecycle, flag-doc,
export-completeness) run by ``python -m gethsharding_tpu.analysis`` and
gated in CI.

Design rules of the framework:

- Every rule is a function ``(corpus) -> list[Finding]`` registered under
  a stable name. Rules read ONLY the corpus (parsed ASTs + repo docs), so
  tests can point them at fixture trees.
- A finding's ``key`` is line-number-free (rule + path + a symbolic
  ident) so routine edits don't churn the committed baseline.
- The baseline file records ACCEPTED findings, each with a one-line
  justification; the gate fails only on findings not in the baseline.
"""

from __future__ import annotations

import ast
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence

# repo-relative path of the committed baseline
BASELINE_REL = "gethsharding_tpu/analysis/baseline.json"


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location.

    ``ident`` is the stable fingerprint component: a symbol-level
    description (class.method, env var name, lock-cycle signature) that
    survives unrelated line churn. ``line`` is for humans only.
    """

    rule: str
    path: str  # repo-relative, '/'-separated
    line: int
    message: str
    ident: str

    @property
    def key(self) -> str:
        return f"{self.rule}::{self.path}::{self.ident}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class SourceFile:
    """One parsed python file plus derived lookup tables."""

    def __init__(self, root: Path, path: Path):
        self.path = path
        self.rel = path.relative_to(root).as_posix()
        self.text = path.read_text(encoding="utf-8")
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(self.text, filename=str(path))
        except SyntaxError as exc:  # surfaced as a finding by the runner
            self.parse_error = exc
        self._imports: Optional[Dict[str, str]] = None

    @property
    def imports(self) -> Dict[str, str]:
        """Local name -> dotted module (or module.symbol) it refers to.

        ``import numpy as np`` -> {"np": "numpy"};
        ``from gethsharding_tpu.ops import bn256_jax`` ->
        {"bn256_jax": "gethsharding_tpu.ops.bn256_jax"};
        ``from x import a as b`` -> {"b": "x.a"}.
        """
        if self._imports is None:
            table: Dict[str, str] = {}
            if self.tree is not None:
                for node in ast.walk(self.tree):
                    if isinstance(node, ast.Import):
                        for alias in node.names:
                            name = alias.asname or alias.name.split(".")[0]
                            table[name] = (alias.name if alias.asname
                                           else alias.name.split(".")[0])
                    elif isinstance(node, ast.ImportFrom):
                        if node.level or not node.module:
                            # relative import: resolve against our package
                            base = self.rel.rsplit("/", 1)[0].replace("/", ".")
                            for _ in range(max(node.level - 1, 0)):
                                base = base.rsplit(".", 1)[0]
                            module = (f"{base}.{node.module}" if node.module
                                      else base)
                        else:
                            module = node.module
                        for alias in node.names:
                            if alias.name == "*":
                                continue
                            name = alias.asname or alias.name
                            table[name] = f"{module}.{alias.name}"
            self._imports = table
        return self._imports

def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Corpus:
    """The parsed source tree the rules run over.

    ``root`` is the repo root; ``files`` covers every ``*.py`` under the
    scanned subtrees. Non-AST inputs the rules need (README.md,
    chip_smoke.py, scripts/, benchmark/) are reachable through ``root``.
    """

    # subtrees scanned for AST rules, relative to root
    DEFAULT_SUBTREES = ("gethsharding_tpu",)
    # extra single files / trees the flag rules also read for env knobs
    DEFAULT_EXTRA = ("chip_smoke.py", "scripts", "benchmark")

    def __init__(self, root: Path, files: Sequence[SourceFile],
                 extra_files: Sequence[SourceFile] = ()):
        self.root = Path(root)
        self.files = list(files)
        self.extra_files = list(extra_files)
        self._by_rel = {f.rel: f for f in self.files}
        for f in self.extra_files:
            self._by_rel.setdefault(f.rel, f)

    @classmethod
    def load(cls, root, subtrees: Sequence[str] = DEFAULT_SUBTREES,
             extra: Sequence[str] = DEFAULT_EXTRA) -> "Corpus":
        root = Path(root)
        files: List[SourceFile] = []
        for sub in subtrees:
            base = root / sub
            if base.is_file():
                files.append(SourceFile(root, base))
                continue
            for path in sorted(base.rglob("*.py")):
                files.append(SourceFile(root, path))
        extras: List[SourceFile] = []
        for sub in extra:
            base = root / sub
            if base.is_file() and base.suffix == ".py":
                extras.append(SourceFile(root, base))
            elif base.is_dir():
                for path in sorted(base.rglob("*.py")):
                    extras.append(SourceFile(root, path))
        return cls(root, files, extras)

    def get(self, rel: str) -> Optional[SourceFile]:
        return self._by_rel.get(rel)

    def find_module(self, dotted: str) -> Optional[SourceFile]:
        """SourceFile for dotted module 'gethsharding_tpu.serving.queue'."""
        rel = dotted.replace(".", "/")
        return self._by_rel.get(rel + ".py") or \
            self._by_rel.get(rel + "/__init__.py")

    def read_doc(self, rel: str) -> Optional[str]:
        path = self.root / rel
        if path.is_file():
            return path.read_text(encoding="utf-8")
        return None


# -- rule registry -----------------------------------------------------------

RuleFn = Callable[[Corpus], List[Finding]]
RULES: Dict[str, RuleFn] = {}
RULE_DOCS: Dict[str, str] = {}


def rule(name: str, doc: str) -> Callable[[RuleFn], RuleFn]:
    def register(fn: RuleFn) -> RuleFn:
        RULES[name] = fn
        RULE_DOCS[name] = doc
        return fn
    return register


def _parse_findings(corpus: Corpus) -> List[Finding]:
    out = []
    for f in corpus.files:
        if f.parse_error is not None:
            out.append(Finding("parse", f.rel, f.parse_error.lineno or 0,
                               f"syntax error: {f.parse_error.msg}",
                               "syntax-error"))
    return out


def run_rules(corpus: Corpus,
              names: Optional[Iterable[str]] = None) -> List[Finding]:
    """Run the selected rules (default: all) and return sorted findings."""
    # rule modules self-register on import; pull them in here so callers
    # (tests, __main__) need only the package
    from gethsharding_tpu.analysis import (  # noqa: F401
        contract, exports, flags, hostsync, layering, lifecycle, locks,
        purity, races)

    selected = list(names) if names is not None else sorted(RULES)
    unknown = [n for n in selected if n not in RULES]
    if unknown:
        raise KeyError(f"unknown rule(s): {', '.join(unknown)} "
                       f"(have: {', '.join(sorted(RULES))})")
    findings = _parse_findings(corpus)
    for name in selected:
        findings.extend(RULES[name](corpus))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.ident))
    return findings


# -- baseline ----------------------------------------------------------------

@dataclass
class Baseline:
    """Accepted findings: key -> one-line justification."""

    entries: Dict[str, str] = field(default_factory=dict)

    @classmethod
    def load(cls, path) -> "Baseline":
        path = Path(path)
        if not path.is_file():
            return cls()
        data = json.loads(path.read_text(encoding="utf-8"))
        return cls(dict(data.get("findings", {})))

    def save(self, path) -> None:
        payload = {
            "_comment": ("shardlint baseline: accepted findings with a "
                         "one-line justification each; the gate fails "
                         "only on keys NOT listed here. Regenerate with "
                         "`python -m gethsharding_tpu.analysis "
                         "--write-baseline` and fill in justifications."),
            "findings": {k: self.entries[k] for k in sorted(self.entries)},
        }
        Path(path).write_text(json.dumps(payload, indent=2) + "\n",
                              encoding="utf-8")

    def split(self, findings: Sequence[Finding]):
        """(new, accepted, stale_keys) against this baseline."""
        keys = {f.key for f in findings}
        new = [f for f in findings if f.key not in self.entries]
        accepted = [f for f in findings if f.key in self.entries]
        stale = sorted(k for k in self.entries if k not in keys)
        return new, accepted, stale


@dataclass
class RunReport:
    findings: List[Finding]
    new: List[Finding]
    accepted: List[Finding]
    stale: List[str]
    elapsed_s: float


def run(root, names: Optional[Iterable[str]] = None,
        baseline_path=None) -> RunReport:
    """Load the corpus at `root`, run rules, diff against the baseline."""
    t0 = time.monotonic()
    corpus = Corpus.load(root)
    findings = run_rules(corpus, names)
    if baseline_path is None:
        baseline_path = Path(root) / BASELINE_REL
    baseline = Baseline.load(baseline_path)
    new, accepted, stale = baseline.split(findings)
    return RunReport(findings, new, accepted, stale,
                     time.monotonic() - t0)
