"""The runtime does not reach up into the static-analysis package.

For each runtime package, every import of every module (parsed, not
executed) names something outside ``dint_tpu.analysis``: the engines
take their configuration from their callers, not from a planner."""
import ast
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parents[1] / "dint_tpu"
FORBIDDEN = "dint_tpu.analysis"


def imported_names(path: pathlib.Path) -> set:
    """Absolute dotted names a module imports, relative imports resolved
    against its package (a ``from X import y`` gives both X and X.y)."""
    parts = ("dint_tpu",) + path.relative_to(PKG).with_suffix("").parts
    package = parts[:-1]              # __init__.py's too: its own package
    out = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = ".".join(package[:len(package) - node.level + 1])
                mod = f"{base}.{node.module}" if node.module else base
            else:
                mod = node.module
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


@pytest.mark.parametrize("package", ["engines", "ops", "tables", "parallel",
                                     "serve", "clients", "monitor", "shim"])
def test_runtime_package_does_not_import_analysis(package):
    modules = sorted((PKG / package).rglob("*.py"))
    assert modules
    bad = {str(m.relative_to(PKG.parent)): sorted(
        n for n in imported_names(m)
        if n == FORBIDDEN or n.startswith(FORBIDDEN + "."))
        for m in modules}
    assert not {m: n for m, n in bad.items() if n}
