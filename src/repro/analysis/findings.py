"""Finding model, suppression comments, and the committed baseline.

A :class:`Finding` is one rule violation at one source location.  Two
escape hatches keep the analyzer deployable on a living tree:

* **Inline suppressions** — a ``# repro-lint: ignore[rule-id]`` comment
  on the flagged line (or alone on the line directly above it) silences
  that rule there; ``# repro-lint: ignore`` with no bracket silences
  every rule on the line.  Suppressions are for *intentional* deviations
  (e.g. a deliberately fixed PRNG seed) and should carry a rationale in
  the same comment.

* **The baseline** — ``.repro-lint-baseline.json`` grandfathers findings
  that predate the analyzer.  ``--check`` fails only on findings NOT in
  the baseline; ``--update-baseline`` rewrites it from the current tree.
  Entries are fingerprinted on (rule, path, symbol, stripped source
  line) rather than line numbers, so unrelated edits don't churn it.
  Baseline entries whose finding has disappeared are *stale* and
  reported so they can be expired with ``--update-baseline``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Tuple

#: rule-id -> one-line description; the single registry every rule
#: family registers into (see docs/ANALYSIS.md for the full catalog).
RULES: Dict[str, str] = {
    "jax-host-time": (
        "wall-clock call (time.time/perf_counter/...) or host span "
        "(repro.obs.span, jax.profiler.TraceAnnotation) inside code "
        "traced by jax.jit/shard_map/pallas_call — the value freezes, "
        "the span fires once, at trace time"),
    "jax-host-random": (
        "np.random / stdlib random inside traced code — untracked "
        "host-side entropy breaks reproducibility and freezes at trace "
        "time; use jax.random with an explicit key"),
    "jax-host-sync": (
        ".item() / float() / np.asarray() on a traced value — aborts "
        "tracing or forces a device sync inside the traced region"),
    "jax-blocking-sync": (
        "float()/.item() on the result of a jitted call — blocks the "
        "host on device compute in a hot path; defer materialization"),
    "prng-constant-key": (
        "jax.random.PRNGKey(<literal>) inside traced code — keys must "
        "enter as parameters or derive via split/fold_in"),
    "prng-key-reuse": (
        "the same PRNG key variable fed to two sampling calls — "
        "identical streams; split or fold_in between uses"),
    "pallas-interpret": (
        "pl.pallas_call wrapper does not plumb an interpret= kwarg — "
        "kernels must stay runnable off-TPU for the ref-oracle tests"),
    "pallas-static-args": (
        "block-size parameters of a pallas_call wrapper not declared in "
        "jax.jit static_argnames — every distinct size retraces or "
        "fails under tracing"),
    "pallas-ref-oracle": (
        "<name>_pallas wrapper has no same-named <name>_ref oracle in "
        "the package's ref.py — the kernel is untestable against "
        "ground truth"),
    "lock-guarded-by": (
        "attribute annotated '# guarded-by: <lock>' mutated outside a "
        "'with self.<lock>:' block"),
    "lock-order-cycle": (
        "cycle in the static lock-acquisition graph — a potential "
        "deadlock under concurrent callers"),
}

_SUPPRESS = re.compile(r"#\s*repro-lint:\s*ignore(?:\[([a-z0-9_,\- ]+)\])?")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str                  # repo-relative, forward slashes
    line: int                  # 1-indexed
    message: str
    symbol: str = ""           # enclosing function/class qualname
    source: str = ""           # stripped source line (baseline anchor)

    def fingerprint(self) -> str:
        basis = f"{self.rule}|{self.path}|{self.symbol}|{self.source}"
        return hashlib.sha1(basis.encode()).hexdigest()[:16]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["fingerprint"] = self.fingerprint()
        return d

    def render(self) -> str:
        sym = f" ({self.symbol})" if self.symbol else ""
        return f"{self.path}:{self.line}: [{self.rule}]{sym} {self.message}"


class Suppressions:
    """Per-file ``# repro-lint: ignore[...]`` comment index."""

    def __init__(self, source: str):
        # line number (1-indexed) -> set of suppressed rule ids
        # (empty set == suppress everything on that line)
        self._by_line: Dict[int, Optional[set]] = {}
        for i, text in enumerate(source.splitlines(), start=1):
            m = _SUPPRESS.search(text)
            if not m:
                continue
            rules = (set(r.strip() for r in m.group(1).split(","))
                     if m.group(1) else None)      # None == all rules
            self._by_line[i] = rules
            # a comment alone on its line also covers the line below
            if text.split("#", 1)[0].strip() == "":
                self._by_line[i + 1] = rules

    def covers(self, line: int, rule: str) -> bool:
        if line not in self._by_line:
            return False
        rules = self._by_line[line]
        return rules is None or rule in rules


def filter_suppressed(findings: Iterable[Finding],
                      sources: Dict[str, str]) -> List[Finding]:
    """Drop findings silenced by an inline comment in their file."""
    cache: Dict[str, Suppressions] = {}
    kept = []
    for f in findings:
        if f.path not in cache:
            cache[f.path] = Suppressions(sources.get(f.path, ""))
        if not cache[f.path].covers(f.line, f.rule):
            kept.append(f)
    return kept


# -- baseline --------------------------------------------------------------

def load_baseline(path: pathlib.Path) -> List[dict]:
    if not path.exists():
        return []
    data = json.loads(path.read_text())
    return list(data.get("findings", []))


def save_baseline(path: pathlib.Path, findings: Iterable[Finding]) -> None:
    entries = sorted((f.to_dict() for f in findings),
                     key=lambda d: (d["path"], d["rule"], d["line"]))
    path.write_text(json.dumps(
        {"comment": "repro-lint grandfathered findings; regenerate with "
                    "scripts/lint.py --update-baseline",
         "findings": entries}, indent=2) + "\n")


def apply_baseline(findings: List[Finding], baseline: List[dict],
                   ) -> Tuple[List[Finding], List[dict]]:
    """Split into (new findings, stale baseline entries)."""
    current = {f.fingerprint() for f in findings}
    known = {e["fingerprint"] for e in baseline}
    new = [f for f in findings if f.fingerprint() not in known]
    stale = [e for e in baseline if e["fingerprint"] not in current]
    return new, stale
