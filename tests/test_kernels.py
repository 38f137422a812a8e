"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (CPU), matching the ref.py implementations to tolerance."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graphs.generators import erdos_renyi, random_geometric_community
from repro.graphs.spectral import lmax_lmin_positive
from repro.kernels.bsr_spmv.ops import (
    bsr_matvec,
    dense_to_bsr,
    power_iteration_lmax_bsr,
)
from repro.kernels.bsr_spmv.ref import bsr_density, bsr_matvec_ref
from repro.kernels.vnge_q.ops import quadratic_q_dense, vnge_q_stats
from repro.kernels.vnge_q.ref import vnge_q_stats_ref
from repro.core.vnge import quadratic_q
from repro.graphs.types import DenseGraph


class TestVngeQKernel:
    @pytest.mark.parametrize("n", [128, 130, 200, 256, 384])
    @pytest.mark.parametrize("dtype", [np.float32, np.float16])
    def test_stats_match_ref(self, n, dtype, rng):
        w = rng.random((n, n)).astype(dtype)
        w = np.triu(w, 1)
        w = (w + w.T).astype(np.float32)
        got = np.asarray(vnge_q_stats(jnp.asarray(w)))
        ref = np.asarray(vnge_q_stats_ref(jnp.asarray(w)))
        np.testing.assert_allclose(got, ref, rtol=3e-5, atol=1e-5)

    @pytest.mark.parametrize("bm,bn", [(64, 64), (128, 128), (64, 128)])
    def test_block_shapes(self, bm, bn, rng):
        w = rng.random((256, 256)).astype(np.float32)
        w = np.triu(w, 1)
        w = w + w.T
        got = np.asarray(vnge_q_stats(jnp.asarray(w), bm=bm, bn=bn))
        ref = np.asarray(vnge_q_stats_ref(jnp.asarray(w)))
        np.testing.assert_allclose(got, ref, rtol=3e-5)

    def test_q_matches_core(self, rng):
        w = rng.random((192, 192)).astype(np.float32)
        w = np.triu(w, 1)
        w = w + w.T
        q_kernel = float(quadratic_q_dense(jnp.asarray(w)))
        q_core = float(quadratic_q(DenseGraph.from_weights(jnp.asarray(w))))
        assert abs(q_kernel - q_core) < 1e-5

    def test_empty_graph(self):
        w = jnp.zeros((128, 128), jnp.float32)
        got = np.asarray(vnge_q_stats(w))
        assert np.allclose(got, 0.0)


class TestBsrSpmv:
    @pytest.mark.parametrize("n,b", [(256, 128), (300, 128), (200, 64)])
    def test_matvec_matches_dense(self, n, b, rng):
        g = random_geometric_community(n, 4, 0.25, 0.01, seed=n)
        w = np.asarray(g.weights)
        m = dense_to_bsr(w, b=b)
        x = rng.random(m.n).astype(np.float32)
        y = np.asarray(bsr_matvec(m, jnp.asarray(x)))
        wp = np.zeros((m.n, m.n), np.float32)
        wp[:n, :n] = w
        np.testing.assert_allclose(y, wp @ x, rtol=1e-4, atol=1e-3)

    def test_matches_ref(self, rng):
        g = erdos_renyi(250, 0.05, seed=9, weighted=True)
        m = dense_to_bsr(np.asarray(g.weights), b=128)
        x = rng.random(m.n).astype(np.float32)
        y_pallas = np.asarray(bsr_matvec(m, jnp.asarray(x)))
        y_ref = np.asarray(bsr_matvec_ref(m, jnp.asarray(x)))
        np.testing.assert_allclose(y_pallas, y_ref, rtol=1e-5, atol=1e-4)

    def test_power_iteration_lambda_max(self):
        g = random_geometric_community(280, 4, 0.3, 0.01, seed=3)
        m = dense_to_bsr(np.asarray(g.weights), b=128)
        lam = float(power_iteration_lmax_bsr(m, num_iters=600, tol=1e-12))
        lam_ref = float(lmax_lmin_positive(g)[0])
        # clustered community spectra have near-multiple top eigenvalues;
        # power iteration converges to ~1e-2 relative there
        assert abs(lam - lam_ref) / lam_ref < 1e-2

    def test_block_sparsity_saves_storage(self):
        g = random_geometric_community(512, 4, 0.4, 0.0, seed=1)
        m = dense_to_bsr(np.asarray(g.weights), b=128)
        assert bsr_density(m) < 1.0  # off-community blocks dropped


class TestParityDiscovery:
    """`kernels.parity` auto-discovery: a new kernel package can never
    silently skip CPU-CI parity coverage — a missing parity.py (or a
    parity module without check_parity) fails by package name."""

    def test_all_kernel_packages_discovered(self):
        from repro.kernels.parity import (
            discover_kernel_packages,
            discover_parity_checks,
        )

        pkgs = discover_kernel_packages()
        # The serving megakernels must both be covered (sparse_tick is
        # a namespace package — no __init__.py — which the filesystem
        # walk must still find).
        assert "stream_tick" in pkgs and "sparse_tick" in pkgs
        checks = discover_parity_checks()
        assert set(checks) == set(pkgs)
        assert all(callable(fn) for fn in checks.values())

    def _tmp_package(self, files):
        import shutil
        from pathlib import Path

        import repro.kernels as root

        base = Path(list(root.__path__)[0])
        pkg = base / "zz_tmp_parity_probe"
        assert not pkg.exists()
        pkg.mkdir()
        for name, text in files.items():
            (pkg / name).write_text(text)
        return pkg, lambda: shutil.rmtree(pkg)

    def test_package_missing_parity_module_fails_by_name(self):
        import importlib

        from repro.kernels.parity import (
            ParityRegistrationError,
            discover_parity_checks,
        )

        pkg, cleanup = self._tmp_package({"ops.py": ""})
        try:
            importlib.invalidate_caches()
            with pytest.raises(ParityRegistrationError,
                               match="zz_tmp_parity_probe"):
                discover_parity_checks()
        finally:
            cleanup()
            importlib.invalidate_caches()

    def test_parity_module_without_check_parity_fails_by_name(self):
        import importlib
        import sys

        from repro.kernels.parity import (
            ParityRegistrationError,
            discover_parity_checks,
        )

        pkg, cleanup = self._tmp_package(
            {"ops.py": "", "parity.py": "not_check_parity = 1\n"})
        try:
            importlib.invalidate_caches()
            with pytest.raises(ParityRegistrationError,
                               match="zz_tmp_parity_probe"):
                discover_parity_checks()
        finally:
            cleanup()
            sys.modules.pop(
                "repro.kernels.zz_tmp_parity_probe.parity", None)
            sys.modules.pop("repro.kernels.zz_tmp_parity_probe", None)
            importlib.invalidate_caches()
