"""Telemetry-name docs lint: code and docs/OBSERVABILITY.md must agree.

Two directions, both enforced as a tier-1 test
(tests/test_observability.py):

* **undocumented** — every telemetry name literal emitted from
  ``qrack_tpu/`` (first argument of ``inc / event / gauge / observe /
  span`` on a telemetry module alias, plus direct ``_COUNTERS["..."]``
  subscripts inside the telemetry package) must match a pattern in the
  first column of a table row in docs/OBSERVABILITY.md.
* **dead** — every documented pattern must match at least one name
  still emitted from the code (``qrack_tpu/`` or ``scripts/`` —
  script-only names keep their doc rows alive but are not themselves
  required to be documented).

``jax.named_scope("qrack....")`` literals and the ``*_KERNEL_NAME``
constants of ``ops/pallas_kernels.py`` are names of the same document
(what a device trace finds a program by) and are held the same way.

Name extraction is AST-based, no imports of the package (so the lint
is jax-free and runs in milliseconds).  f-string names contribute
their literal *prefix* up to the first interpolation
(``f"gate.{eng}..."`` -> prefix ``gate.``); calls whose first argument
is a bare variable are skipped.

Doc patterns are the backticked tokens of each row's first cell.
``<x>`` and ``*`` are wildcards; ``{a,b}`` expands; a ``/`` in the
final segment expands alternatives (``compile.<c>.hit/miss/eviction``
-> three patterns).  Matching is prefix-compatibility: a code prefix P
and a pattern's literal text L (up to its first wildcard) are
compatible iff one startswith the other; exact names and wildcard-free
patterns must contain/equal accordingly.

Usage: python scripts/check_telemetry_docs.py  (exit 0 = clean).
"""

from __future__ import annotations

import ast
import itertools
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "OBSERVABILITY.md")

TELE_FUNCS = {"inc", "event", "gauge", "observe", "span", "record_span"}
# aliases under which the telemetry module is imported across the tree
TELE_ALIASES = {"telemetry", "_tele", "tele", "_telemetry"}


# -- code-side extraction ----------------------------------------------


def _first_arg_name(call: ast.Call):
    """(text, is_prefix) for a literal/f-string first arg, else None."""
    if not call.args:
        return None
    a = call.args[0]
    if isinstance(a, ast.Constant) and isinstance(a.value, str):
        return a.value, False
    if isinstance(a, ast.JoinedStr):
        prefix = ""
        for part in a.values:
            if isinstance(part, ast.Constant) and isinstance(part.value, str):
                prefix += part.value
            else:
                break
        if prefix:
            return prefix, True
        return None
    return None


def _is_tele_call(func) -> bool:
    if isinstance(func, ast.Attribute) and func.attr == "named_scope":
        return True  # jax.named_scope: a name a device trace reads
    if isinstance(func, ast.Attribute) and func.attr in TELE_FUNCS:
        v = func.value
        if isinstance(v, ast.Name):
            return v.id in TELE_ALIASES
        if isinstance(v, ast.Attribute):  # e.g. tqe._tele.inc(...)
            return v.attr in TELE_ALIASES
    return False


def extract_names(path: str, in_telemetry_pkg: bool):
    """Yield (text, is_prefix, lineno) telemetry names from one file."""
    with open(path, encoding="utf-8") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            return
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            bare = (in_telemetry_pkg and isinstance(node.func, ast.Name)
                    and node.func.id in TELE_FUNCS)
            if _is_tele_call(node.func) or bare:
                got = _first_arg_name(node)
                if got is not None:
                    yield got[0], got[1], node.lineno
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
              and isinstance(node.targets[0], ast.Name)
              and node.targets[0].id.endswith("_KERNEL_NAME")
              and isinstance(node.value, ast.Constant)):
            yield node.value.value, False, node.lineno
        elif isinstance(node, ast.Subscript) and in_telemetry_pkg:
            v, s = node.value, node.slice
            if (isinstance(v, ast.Name) and v.id == "_COUNTERS"
                    and isinstance(s, ast.Constant)
                    and isinstance(s.value, str)):
                yield s.value, False, node.lineno
        elif isinstance(node, ast.Call):  # _COUNTERS.get("...")
            pass


def _counters_get_names(path: str):
    """_COUNTERS.get("name", ...) reads double as write sites here."""
    with open(path, encoding="utf-8") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            return
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "_COUNTERS"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            yield node.args[0].value, False, node.lineno


def scan_tree(root: str, telemetry_pkg_prefix=None):
    """[(text, is_prefix, file, line)] over every .py under root."""
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in {"__pycache__", ".git"}]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO)
            in_pkg = bool(telemetry_pkg_prefix
                          and rel.startswith(telemetry_pkg_prefix))
            for text, pref, line in extract_names(path, in_pkg):
                out.append((text, pref, rel, line))
            if in_pkg:
                for text, pref, line in _counters_get_names(path):
                    out.append((text, pref, rel, line))
    return out


# -- doc-side extraction -----------------------------------------------


def _expand_braces(tok: str):
    m = re.search(r"\{([^{}]+)\}", tok)
    if not m or "," not in m.group(1):
        return [tok]
    alts = m.group(1).split(",")
    out = []
    for alt in alts:
        out.extend(_expand_braces(tok[:m.start()] + alt + tok[m.end():]))
    return out


def _expand_slashes(tok: str):
    """a.b.hit/miss/eviction -> a.b.hit, a.b.miss, a.b.eviction."""
    if "/" not in tok:
        return [tok]
    parts = tok.split("/")
    head = parts[0]
    cut = head.rfind(".") + 1
    base = head[:cut]
    return [head] + [base + p for p in parts[1:]]


def doc_patterns(doc_path: str):
    """[(literal_text, has_wildcard, lineno, raw_token)] from table rows."""
    pats = []
    with open(doc_path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line.startswith("|") or set(line) <= {"|", "-", " "}:
                continue
            cells = line.split("|")
            if len(cells) < 2:
                continue
            first = cells[1]
            for tok in re.findall(r"`([^`]+)`", first):
                if ("." not in tok and "*" not in tok
                        and not tok.startswith("qrack_")):
                    continue  # env var / prose, not a telemetry name
                if not re.fullmatch(r"[A-Za-z0-9_.<>{}*,/-]+", tok):
                    continue
                for t1 in _expand_braces(tok):
                    for t2 in _expand_slashes(t1):
                        m = re.search(r"[<*]", t2)
                        if m:
                            if m.start() == 0:
                                continue  # empty prefix matches all: ban
                            pats.append((t2[:m.start()], True, lineno, tok))
                        else:
                            pats.append((t2, False, lineno, tok))
    return pats


# -- matching ----------------------------------------------------------


def _matches(name_text, name_is_prefix, pat_text, pat_wild) -> bool:
    if not name_is_prefix and not pat_wild:
        return name_text == pat_text
    if not name_is_prefix and pat_wild:
        return name_text.startswith(pat_text)
    if name_is_prefix and not pat_wild:
        return pat_text.startswith(name_text)
    return (name_text.startswith(pat_text)
            or pat_text.startswith(name_text))


def main() -> int:
    lib = scan_tree(os.path.join(REPO, "qrack_tpu"),
                    telemetry_pkg_prefix=os.path.join("qrack_tpu",
                                                      "telemetry"))
    extra = scan_tree(os.path.join(REPO, "scripts"))
    pats = doc_patterns(DOC)
    if not pats:
        print(f"FAIL: no telemetry-name patterns found in {DOC}")
        return 1

    failures = []
    for text, pref, rel, line in lib:
        if not any(_matches(text, pref, pt, pw) for pt, pw, _, _ in pats):
            kind = "prefix" if pref else "name"
            failures.append(
                f"undocumented {kind} {text!r} ({rel}:{line}) — add a row "
                "to docs/OBSERVABILITY.md")

    everything = lib + extra
    for pt, pw, lineno, raw in sorted(set(pats), key=lambda p: p[2]):
        if not any(_matches(t, pr, pt, pw) for t, pr, _, _ in everything):
            failures.append(
                f"dead documented pattern `{raw}` "
                f"(docs/OBSERVABILITY.md:{lineno}) — no code site emits a "
                "matching name")

    if failures:
        for msg in sorted(set(failures)):
            print("FAIL:", msg)
        print(f"{len(set(failures))} problem(s).")
        return 1
    print(f"ok: {len(lib)} code name(s) covered by {len(pats)} documented "
          "pattern(s); no dead patterns.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
