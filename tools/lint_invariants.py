#!/usr/bin/env python
"""AST lint forbidding determinism hazards in the search/reconcile core.

The tuning stack's central contract is bit-identical replay: a fixed seed
must reproduce the same search history regardless of measurement time or
host entropy (see ``core/tuner.py``). That contract dies quietly
— an unseeded RNG here, a wall-clock-keyed decision there — so this lint
makes the hazards structural errors in CI instead of flaky-test archaeology:

- ``unseeded-rng``     ``np.random.default_rng()`` with no seed, any use of
                       the global ``np.random.*`` / stdlib ``random.*``
                       draw functions (module-global state, process-wide
                       and import-order dependent).
- ``wall-clock``       ``time.time()`` / ``datetime.now()`` and friends.
                       Timing a measurement span is legitimate —
                       ``time.perf_counter`` / ``time.monotonic`` are the
                       blessed clocks and are not flagged — but calendar
                       time feeding logic is not reproducible.
- ``dict-order-rng``   an RNG draw (``integers``/``choice``/``shuffle``/
                       ``permutation``/...) consuming ``set(...)`` or a
                       dict view (``.keys()``/``.values()``/``.items()``)
                       — iteration order of a set is salted per process,
                       and a dict built in varying order silently reorders
                       the candidate list behind a "deterministic" draw.
- ``identity-cache-key`` cache-key construction from object *identity*
                       instead of value: any ``id(...)`` call (identity is
                       process- and allocation-dependent — two equal
                       schedules get different keys, and a recycled address
                       silently aliases two different ones), and
                       ``repr(...)`` used as a subscript/lookup key (the
                       default ``object.__repr__`` embeds the address;
                       content keys must come from explicit signatures —
                       ``Schedule.signature()`` / ``KernelParams
                       .signature()`` — see ``core/build_cache.py``).
- ``policy-wall-clock`` ANY clock call — including the otherwise-blessed
                       ``time.monotonic()`` / ``time.perf_counter()`` —
                       inside a class named ``*Policy`` or ``*Ledger``.
                       Adaptation policies (scheduler depth, budget
                       reallocation) must decide from *recorded* span
                       intervals and per-driver state, never a live clock:
                       a policy that reads the clock directly cannot be
                       replayed under a scripted clock, breaking the
                       adaptive-run reproducibility contract
                       (see ``core/measure_scheduler.AdaptiveDepthPolicy``).

Escape hatch: append ``# lint: allow(<rule>)`` on the offending line when
the use is provably safe (e.g. a deliberately wall-clock-stamped log line).

Usage: ``python tools/lint_invariants.py src/repro/core [more paths ...]``
Exits 1 when any finding survives, printing ``path:line: rule: message``.
"""

from __future__ import annotations

import ast
import os
import re
import sys

RNG_DRAW_METHODS = {"integers", "random", "choice", "shuffle", "permutation",
                    "uniform", "normal", "standard_normal", "bytes"}
STDLIB_RANDOM_FNS = {"random", "randint", "randrange", "choice", "choices",
                     "shuffle", "sample", "uniform", "gauss", "seed",
                     "betavariate", "normalvariate", "getrandbits"}
WALL_CLOCK = {("time", "time"), ("time", "ctime"), ("time", "localtime"),
              ("time", "gmtime"), ("datetime", "now"), ("datetime", "today"),
              ("datetime", "utcnow"), ("date", "today")}
# all clock reads, including the span-blessed monotonic clocks — none may
# appear inside *Policy / *Ledger classes (policy-wall-clock rule)
ANY_CLOCK = WALL_CLOCK | {("time", "monotonic"), ("time", "perf_counter"),
                          ("time", "monotonic_ns"),
                          ("time", "perf_counter_ns"),
                          ("time", "process_time"), ("time", "time_ns")}
# class-name suffixes whose bodies must be clock-free (adaptation layer)
_CLOCK_FREE_CLASS_RE = re.compile(r"(Policy|Ledger)$")

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([a-z0-9_,\- ]+)\)")


def _dotted(node: ast.AST) -> list[str]:
    """The attribute chain of a node as names, e.g. np.random.default_rng
    -> ['np', 'random', 'default_rng']; [] for non-name/attribute nodes."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return []


def _consumes_unordered(node: ast.AST) -> bool:
    """Does any subexpression produce a set or dict view (salted /
    insertion-order-dependent iteration)?"""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        if isinstance(sub.func, ast.Attribute) and \
                sub.func.attr in ("keys", "values", "items"):
            return True
        if isinstance(sub.func, ast.Name) and \
                sub.func.id in ("set", "frozenset"):
            return True
        for comp in ast.walk(sub):
            if isinstance(comp, ast.SetComp):
                return True
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, filename: str):
        self.filename = filename
        self.findings: list[tuple[int, str, str]] = []
        self._class_stack: list[str] = []

    def _flag(self, node: ast.AST, rule: str, message: str) -> None:
        self.findings.append((node.lineno, rule, message))

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()

    def _in_clock_free_class(self) -> str | None:
        for name in self._class_stack:
            if _CLOCK_FREE_CLASS_RE.search(name):
                return name
        return None

    @staticmethod
    def _calls_repr(node: ast.AST) -> bool:
        """Does any subexpression call repr() (or __repr__ directly)?
        f-string ``!r`` conversions count too — they lower to the same
        default repr."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                if isinstance(sub.func, ast.Name) and sub.func.id == "repr":
                    return True
                if isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "__repr__":
                    return True
            if isinstance(sub, ast.FormattedValue) and sub.conversion == 114:
                return True  # f"{x!r}"
        return False

    def visit_Subscript(self, node: ast.Subscript) -> None:
        # cache[repr(x)] / cache[(repr(a), b)]: a default repr embedding
        # the object address is an identity key in value-key clothing
        if self._calls_repr(node.slice):
            self._flag(node, "identity-cache-key",
                       "repr(...) inside a subscript key: the default "
                       "object.__repr__ embeds the address; use an "
                       "explicit value-derived signature instead")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        chain = _dotted(node.func)
        joined = ".".join(chain)
        # -- unseeded-rng --
        if chain and chain[-1] == "default_rng" and not node.args \
                and not node.keywords:
            self._flag(node, "unseeded-rng",
                       f"{joined}() without a seed draws process entropy; "
                       f"thread the caller's seed through")
        elif len(chain) >= 2 and chain[0] == "random" \
                and chain[-1] in STDLIB_RANDOM_FNS:
            self._flag(node, "unseeded-rng",
                       f"stdlib {joined}() uses module-global RNG state; "
                       f"use a seeded np.random.Generator")
        elif len(chain) >= 3 and chain[-2] == "random" \
                and chain[0] in ("np", "numpy") \
                and chain[-1] in (RNG_DRAW_METHODS | {"rand", "randn",
                                                      "randint", "seed"}):
            self._flag(node, "unseeded-rng",
                       f"global {joined}() uses np.random's process-wide "
                       f"state; use a seeded Generator instance")
        # -- wall-clock --
        if len(chain) >= 2 and (chain[-2], chain[-1]) in WALL_CLOCK:
            self._flag(node, "wall-clock",
                       f"{joined}() reads calendar time; use "
                       f"time.perf_counter()/time.monotonic() for spans, "
                       f"or pass timestamps in explicitly")
        # -- policy-wall-clock --
        if len(chain) >= 2 and (chain[-2], chain[-1]) in ANY_CLOCK:
            cls = self._in_clock_free_class()
            if cls is not None:
                self._flag(node, "policy-wall-clock",
                           f"{joined}() inside {cls}: adaptation policies "
                           f"must decide from recorded span intervals "
                           f"(e.g. MeasureScheduler.busy_fraction), never "
                           f"a live clock — adaptive runs must replay "
                           f"under a scripted clock")
        # -- identity-cache-key (id) --
        if chain == ["id"]:
            self._flag(node, "identity-cache-key",
                       "id() keys on object identity, not value — two "
                       "equal schedules get different keys and a recycled "
                       "address aliases different ones; build content keys "
                       "from signatures (Schedule.signature() / "
                       "KernelParams.signature())")
        # -- identity-cache-key (repr used as a lookup key) --
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("get", "setdefault", "pop") \
                and node.args and self._calls_repr(node.args[0]):
            self._flag(node, "identity-cache-key",
                       "repr(...) as a lookup key: the default "
                       "object.__repr__ embeds the address; use an "
                       "explicit value-derived signature instead")
        # -- dict-order-rng --
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in RNG_DRAW_METHODS \
                and chain[:1] != ["random"]:
            receiver = _dotted(node.func.value)
            looks_rng = any("rng" in p.lower() or "random" in p.lower()
                            for p in receiver) or not receiver
            if looks_rng and any(_consumes_unordered(a) for a in node.args):
                self._flag(node, "dict-order-rng",
                           f"RNG draw {joined}(...) consumes a set or dict "
                           f"view; materialize a deterministically-ordered "
                           f"list (e.g. sorted(...) or dict.fromkeys) first")
        self.generic_visit(node)


def lint_source(source: str, filename: str) -> list[str]:
    """Lint one module's source; returns 'path:line: rule: message' rows
    (suppressed rows excluded)."""
    try:
        tree = ast.parse(source, filename=filename)
    except SyntaxError as exc:
        return [f"{filename}:{exc.lineno or 0}: parse-error: {exc.msg}"]
    visitor = _Visitor(filename)
    visitor.visit(tree)
    lines = source.splitlines()
    out = []
    for lineno, rule, message in sorted(visitor.findings):
        line = lines[lineno - 1] if 0 < lineno <= len(lines) else ""
        m = _ALLOW_RE.search(line)
        allowed = {s.strip() for s in m.group(1).split(",")} if m else set()
        if rule in allowed:
            continue
        out.append(f"{filename}:{lineno}: {rule}: {message}")
    return out


def lint_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path)


def _iter_py(paths: list[str]):
    for path in paths:
        if os.path.isdir(path):
            for root, _dirs, files in sorted(os.walk(path)):
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)
        else:
            yield path


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__.strip().splitlines()[-2].strip())
        return 2
    findings: list[str] = []
    n_files = 0
    for path in _iter_py(argv):
        n_files += 1
        findings.extend(lint_file(path))
    for row in findings:
        print(row)
    status = "FAILED" if findings else "clean"
    print(f"# lint_invariants: {n_files} file(s), "
          f"{len(findings)} finding(s) — {status}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
