"""Cross-package private-import guard for ``src/repro/``.

An ``_underscore`` name is a package-internal contract. Importing one
from a *different* ``repro.<pkg>`` subpackage couples two packages
through an interface nobody promised to keep — exactly the
``serve.bc_service`` → ``approx.driver._single_host_step`` leak the
``repro.bc`` facade redesign removed. This script fails (exit 1) when
any module under ``src/repro/`` does it again:

* ``from repro.other.mod import _name``        — private symbol
* ``from repro.other import _mod`` / ``import repro.other._mod``
                                               — private module
* relative imports are resolved first; imports *within* one subpackage
  (``repro.core.mfbc`` → ``repro.core._helpers``) stay legal, as does
  aliasing a public name to a private local (``import x as _x``).

CI runs this next to ruff (see .github/workflows/ci.yml); run locally
with

    python tools/check_private_imports.py
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"
ROOT_PKG = "repro"


def _module_name(py: Path) -> str:
    """Dotted module name of a file under src/ (pkg/__init__.py → pkg)."""
    rel = py.relative_to(SRC).with_suffix("")
    parts = list(rel.parts)
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _subpackage(dotted: str) -> str:
    """The ``repro.<pkg>`` grouping key: '' for repro itself and its
    top-level modules, else the first component below it."""
    parts = dotted.split(".")
    if len(parts) < 2 or parts[0] != ROOT_PKG:
        return ""
    return parts[1]


def _resolve_relative(importer: str, is_pkg: bool, module: str | None,
                      level: int) -> str | None:
    """Absolute dotted target of a level-N relative import, or None."""
    base = importer.split(".")
    if not is_pkg:
        base = base[:-1]
    if level > 1:
        base = base[:len(base) - (level - 1)]
    if not base:
        return None
    return ".".join(base + ([module] if module else []))


def _violations(py: Path) -> list[str]:
    importer = _module_name(py)
    importer_pkg = _subpackage(importer)
    # the importing file's *module* subpackage; __init__ of repro itself
    # has importer == "repro" → pkg "" (cross to everything below it is
    # fine: a facade package re-exporting is the public surface)
    try:
        tree = ast.parse(py.read_text(), filename=str(py))
    except SyntaxError as e:  # pragma: no cover — ruff gates syntax first
        return [f"{py}: syntax error: {e}"]
    errs: list[str] = []

    def check_target(target: str, names: list[str], lineno: int) -> None:
        if not target.startswith(ROOT_PKG + ".") and target != ROOT_PKG:
            return  # third-party / stdlib: not ours to police
        target_pkg = _subpackage(target)
        if target_pkg == importer_pkg:
            return  # same subpackage: private sharing is allowed
        # every dotted component below the root package counts — a
        # top-level private module (repro._util) is just as internal
        private = [p for p in target.split(".")[1:] if p.startswith("_")]
        private += [s for s in names
                    if s.startswith("_") and not s.startswith("__")]
        home = (f"{ROOT_PKG}.{importer_pkg}" if importer_pkg else ROOT_PKG)
        for name in private:
            errs.append(f"{py.relative_to(REPO)}:{lineno}: cross-package "
                        f"private import {name!r} from {target!r} "
                        f"(importer package {home})")

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                check_target(alias.name, [], node.lineno)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                target = _resolve_relative(
                    importer, py.name == "__init__.py", node.module,
                    node.level)
                if target is None:
                    continue
            else:
                target = node.module or ""
            check_target(target, [a.name for a in node.names], node.lineno)
    return errs


def main() -> int:
    files = sorted(p for p in (SRC / ROOT_PKG).rglob("*.py")
                   if "__pycache__" not in p.parts)
    if not files:
        print("check_private_imports: no files under src/repro",
              file=sys.stderr)
        return 1
    errors: list[str] = []
    for f in files:
        errors += _violations(f)
    if errors:
        for e in errors:
            print(f"check_private_imports: LEAK  {e}", file=sys.stderr)
        print(f"check_private_imports: {len(errors)} cross-package private "
              f"import(s) in {len(files)} file(s)", file=sys.stderr)
        return 1
    print(f"check_private_imports: OK — {len(files)} files, no "
          f"cross-package private imports")
    return 0


if __name__ == "__main__":
    sys.exit(main())
