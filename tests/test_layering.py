"""The packages import one way: serve -> models -> kernels.

``repro.models`` owns the model and how its caches live on the device,
the paged KV pools included (``repro.models.paged_cache``); serving and
training build on it.  A module of ``repro.models`` that imported
``repro.serve`` or ``repro.train`` would make the arrow point both ways
again.  The imports are read from the source with ``ast``, at module
level and inside functions alike, so a docstring naming a module does
not count.
"""

import ast
import pathlib

import repro.models

FORBIDDEN = ("repro.serve", "repro.train")


def _imported(path: pathlib.Path, package: str):
    """Every module name ``path`` imports, relative imports resolved
    against ``package``; ``from a import b`` yields ``a`` and ``a.b``."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parts = package.split(".")[: len(package.split("."))
                                           - node.level + 1]
                base = ".".join(parts + ([base] if base else []))
            yield base
            yield from (f"{base}.{alias.name}" for alias in node.names)


def test_models_import_neither_serve_nor_train():
    root = pathlib.Path(repro.models.__file__).parent
    bad = []
    for path in sorted(root.rglob("*.py")):
        package = ".".join(
            ("repro", "models") + path.relative_to(root).parent.parts)
        for name in _imported(path, package):
            if any(name == f or name.startswith(f + ".")
                   for f in FORBIDDEN):
                bad.append(f"{path.relative_to(root)}: {name}")
    assert not bad, bad
