"""Finds the parts of a cell by name: `<root>/bench/<kind>/<name>.py`.

A graph model (`graphs`), a request loop (`loops`) and a metric reader
(`metrics`) are each a file of their own, so a cell made of new files
needs no edit to a file that is already there.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

__all__ = ["CellError", "path", "module"]


class CellError(Exception):
    """The cell cannot run here: unknown name, missing file, no chip."""


def path(root: Path, kind: str, name: str) -> Path:
    """The file of part `name` of kind `kind`; CellError where none is."""
    p = Path(root) / "bench" / kind / f"{name}.py"
    if not p.is_file():
        known = sorted(q.stem for q in p.parent.glob("*.py"))
        raise CellError(f"no {kind} file {name!r} under {p.parent}; "
                        f"known: {known}")
    return p


def module(root: Path, kind: str, name: str):
    """Part `name` of kind `kind`, loaded from its file."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path(root, kind, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
