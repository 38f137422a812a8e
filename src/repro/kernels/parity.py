"""Kernel parity registry: discovery + the shared comparison helper.

Every kernel package under ``repro.kernels`` ships a ``parity.py``
exposing ``check_parity()`` — a small-input check of the interpret
path against the package's pure-jnp oracle that raises on mismatch
(`repro.analysis.vmem` runs them all).

`discover_parity_checks` walks the package with pkgutil, so a new
kernel package can never silently skip CPU-CI parity coverage: a
missing or malformed ``parity.py`` is a hard `ParityRegistrationError`
naming the offending kernel.  The `repro.analysis.lint`
``kernel-package-triple`` rule enforces the same layout statically.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from typing import Callable, Dict

import numpy as np

ParityFn = Callable[[], None]


class ParityRegistrationError(RuntimeError):
    """A kernel package is missing its parity registration."""


def assert_close(name: str, got, want, atol: float,
                 rtol: float = 1e-5) -> None:
    """Shared parity assertion: interpret path vs jnp oracle."""
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=atol, rtol=rtol,
        err_msg=f"{name}: interpret path drifted from its jnp oracle")


def discover_kernel_packages() -> Dict[str, Path]:
    """Kernel package directories under ``repro.kernels``, by name.

    A directory counts as a kernel package if it holds an ``ops.py``
    (the public-op wrapper every kernel must expose). Filesystem-based
    rather than pkgutil so namespace packages (no ``__init__.py``) are
    found too.
    """
    import repro.kernels as root

    pkgs: Dict[str, Path] = {}
    for base in root.__path__:
        for child in sorted(Path(base).iterdir()):
            if child.is_dir() and (child / "ops.py").is_file():
                pkgs[child.name] = child
    return dict(sorted(pkgs.items()))


def discover_parity_checks() -> Dict[str, ParityFn]:
    """All kernel packages' ``check_parity`` entry points, by package
    name, in sorted order. Raises `ParityRegistrationError` if any
    kernel package lacks one."""
    checks: Dict[str, ParityFn] = {}
    for name in discover_kernel_packages():
        modname = f"repro.kernels.{name}.parity"
        try:
            mod = importlib.import_module(modname)
        except ModuleNotFoundError as exc:
            raise ParityRegistrationError(
                f"kernel package 'repro.kernels.{name}' has no "
                f"parity module ({modname}); every kernel must ship "
                "kernel.py / ops.py / ref.py / parity.py so CPU CI "
                "covers its interpret path") from exc
        fn = getattr(mod, "check_parity", None)
        if not callable(fn):
            raise ParityRegistrationError(
                f"{modname} does not define check_parity()")
        checks[name] = fn
    return dict(sorted(checks.items()))
