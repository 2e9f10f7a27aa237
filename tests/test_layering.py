"""The packages under the engine do not import it: `ops/`, `models/` and
`observability/` are what the engine's programs and counters are built
from, and a file of theirs that reached back into `kserve_tpu.engine` would
make a new model or kernel edit the scheduler again (engine/limits.py,
engine/work.py and ops/kv_write.py are where such edits land)."""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "kserve_tpu"


def _imports(path: pathlib.Path):
    """Every module a file imports, absolute, with the line: relative
    imports resolved against the file's package, lazy ones included."""
    package = path.relative_to(ROOT.parent).with_suffix("").parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else ()
            module = ".".join((*base, *filter(None, [node.module])))
            yield module, node.lineno
            for alias in node.names:  # `from .. import engine`
                yield f"{module}.{alias.name}", node.lineno


@pytest.mark.parametrize("package", ["ops", "models", "observability"])
def test_nothing_under_the_engine_imports_it(package):
    files = sorted((ROOT / package).rglob("*.py"))
    assert files, package
    reaching = sorted({
        f"{path.relative_to(ROOT.parent)}:{line}"
        for path in files for module, line in _imports(path)
        if module == "kserve_tpu.engine"
        or module.startswith("kserve_tpu.engine.")})
    assert not reaching, f"imports of kserve_tpu.engine: {reaching}"
