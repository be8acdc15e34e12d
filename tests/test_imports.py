"""Every module under ``repro`` imports: a deletion that leaves an import
dangling fails here, not on the first run that reaches the module."""
import importlib
import os
import pkgutil

import repro


def _module_names():
    # ``pkgutil.walk_packages`` does not descend into the namespace-style
    # subpackages (``launch/``, ``roofline/`` have no ``__init__.py``), so
    # list each directory's modules on its own.
    root = repro.__path__[0]
    for dirpath, dirnames, _ in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        rel = os.path.relpath(dirpath, root)
        prefix = "repro." if rel == "." else f"repro.{rel.replace(os.sep, '.')}."
        for m in pkgutil.iter_modules([dirpath], prefix):
            yield m.name


def test_every_repro_module_imports():
    names = sorted(set(_module_names()))
    assert {"repro.bc.solve", "repro.launch.checkpoint",
            "repro.roofline.hlo_parse"} <= set(names)
    for name in names:
        importlib.import_module(name)
