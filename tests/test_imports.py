"""Imports that no other test makes.

- Every script (examples, `benchmarks/`, `chip_smoke.py`) is parsed, not
  run, and each of its imports from `repro` or `benchmarks` must name a
  module and attributes that exist, so a script cannot keep importing a
  module after it moved or went.
- Every package of `repro` is imported with all its modules in a fresh
  interpreter, so an import cycle that a shared test process hides (one
  that another package happened to break by loading first) shows up.
"""
import ast
import importlib
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = ROOT / "src"

SCRIPTS = sorted(
    [ROOT / "examples" / f"{n}.py"
     for n in ("quickstart", "anomaly_detection", "serve_streams")]
    + list((ROOT / "benchmarks").glob("*.py"))
    + [ROOT / "chip_smoke.py"])

PACKAGES = ("graphs", "core", "kernels", "engine", "serving", "fleet",
            "analysis", "distributed", "baselines")

LOCAL = ("repro", "benchmarks")


def _local_imports(path):
    """``(module, [names])`` for every import of a local package in the
    file, at any depth (function-level imports included)."""
    out = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out += [(a.name, []) for a in node.names
                    if a.name.split(".")[0] in LOCAL]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and node.module.split(".")[0] in LOCAL:
            out.append((node.module, [a.name for a in node.names]))
    return out


@pytest.mark.parametrize("script", SCRIPTS,
                         ids=[str(p.relative_to(ROOT)) for p in SCRIPTS])
def test_script_imports_resolve(script, monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    monkeypatch.syspath_prepend(str(SRC))
    for module, names in _local_imports(script):
        mod = importlib.import_module(module)
        for name in names:
            assert hasattr(mod, name) or importlib.util.find_spec(
                f"{module}.{name}") is not None, \
                f"{script.name}: {module} has no {name!r}"


def _modules(package):
    """Every module of ``repro.<package>``, from its files (namespace
    subpackages included; ``__main__`` is a program, not a module)."""
    base = SRC / "repro" / package
    mods = []
    for py in sorted(base.rglob("*.py")):
        if py.name == "__main__.py":
            continue
        parts = py.relative_to(SRC).with_suffix("").parts
        if parts[-1] == "__init__":
            parts = parts[:-1]
        mods.append(".".join(parts))
    return mods


@pytest.mark.parametrize("package", PACKAGES)
def test_package_imports_alone(package):
    code = "import importlib\n" + "".join(
        f"importlib.import_module({m!r})\n"
        for m in [f"repro.{package}"] + _modules(package))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=str(ROOT), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
