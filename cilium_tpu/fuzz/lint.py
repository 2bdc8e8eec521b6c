"""Seed-determinism lint: no unseeded RNG in the fuzz/chaos tooling.

A fuzz failure is only as good as its repro, and a repro is only as
good as the seed chain: ONE argument-less Generator construction (or
a legacy global-state numpy/stdlib random call) anywhere on a fuzz
code path makes "reproducible from the logged seed alone" a lie.
This module is the grep-able guarantee: a source-level scan for the
unseeded idioms, run by tests over the fuzzer package and the seeded
tooling (tools/policyfuzz.py, tools/chaos_storm.py, and bench.py's
config-5 world builders, Zipf and flow-pool samplers included).

The scan is intentionally source-text based (not runtime): an
unseeded call on a COLD path (an error branch, a rarely-taken event)
is exactly the one a runtime probe misses.
"""

from __future__ import annotations

import os
import re
from typing import Iterable, List, Tuple

# the unseeded idioms: argument-less Generator construction, the
# legacy numpy global-state API, and the stdlib module-level
# functions (random.Random(x) with a seed is fine; bare random.* is
# process-global state)
_PATTERNS = (
    re.compile(r"\bdefault_rng\(\s*\)"),
    re.compile(r"\bRandomState\(\s*\)"),
    re.compile(
        r"\bnp\.random\.(rand|randn|randint|random|random_sample|"
        r"choice|shuffle|permutation|uniform|normal|poisson|zipf)\("
    ),
    re.compile(
        r"(?<![\w.])random\.(random|randint|randrange|choice|"
        r"choices|shuffle|sample|uniform|gauss|expovariate)\("
    ),
)

# comment-only and annotation lines don't call anything
_SKIP = re.compile(r"^\s*#")


def unseeded_rng_calls(
    paths: Iterable[str],
) -> List[Tuple[str, int, str]]:
    """Scan python sources for unseeded-RNG idioms.  Returns
    [(path, lineno, line)] — empty means the seed chain is intact.
    Directories recurse over ``*.py``."""
    out: List[Tuple[str, int, str]] = []
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                files.extend(
                    os.path.join(root, n)
                    for n in sorted(names)
                    if n.endswith(".py")
                )
        else:
            files.append(p)
    for path in files:
        with open(path, "r", encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                if _SKIP.match(line):
                    continue
                for pat in _PATTERNS:
                    if pat.search(line):
                        out.append((path, lineno, line.rstrip()))
                        break
    return out


def fuzz_lint_paths(repo_root: str | None = None) -> List[str]:
    """The canonical lint surface: the fuzzer package plus every
    tool the seed satellite plumbs (--seed) through."""
    if repo_root is None:
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    return [
        os.path.join(repo_root, "cilium_tpu", "fuzz"),
        os.path.join(repo_root, "tools", "policyfuzz.py"),
        os.path.join(repo_root, "tools", "chaos_storm.py"),
        os.path.join(repo_root, "bench.py"),
    ]
