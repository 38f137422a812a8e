"""Fast serving smoke (non-slow, single host process): a tiny
FingerService in *each* placement mode — multipod via a 1×N host mesh —
runs a few ticks, answers a top-k query, and round-trips save/restore
with identical resumed scores.

This is the CI canary for the declarative serving surface: it exercises
config validation, plan compilation, both ingestion modes, the
checkpoint policy wiring, and the placement-specific top-k paths in a
few seconds on one CPU device.
"""
import numpy as np
import pytest

import jax

from repro.distributed.sharding import auto_mesh
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.serving import (
    CheckpointPolicy,
    FingerService,
    ServiceConfig,
    TopKSpec,
)

B, N_PAD, K_PAD, TICKS = 8, 16, 3, 4


def _graphs():
    return [erdos_renyi(8 + 2 * (s % 4), 0.25, seed=s, weighted=True)
            for s in range(B)]


def _ticks(seed=0):
    graphs = _graphs()
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(TICKS):
        ds = []
        for g in graphs:
            n = g.n_nodes
            i, j = sorted(rng.choice(n, 2, replace=False).tolist())
            w_old = float(np.asarray(g.weights)[i, j])
            ds.append(GraphDelta.from_arrays(
                [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
                n_nodes=n, n_pad=N_PAD, k_pad=K_PAD))
        out.append(ds)
    return out


def _mesh_for(placement):
    if placement == "local":
        return None
    if placement == "sharded":
        return auto_mesh((jax.device_count(),), ("data",))
    # multipod smoke runs on a 1×N host mesh — the pod axis is size 1,
    # which still exercises the ("pod", "data") shard_map + per-pod
    # top-k code path.
    return auto_mesh((1, jax.device_count()), ("pod", "data"))


@pytest.mark.parametrize("placement,ingestion", [
    ("local", "sync"),
    ("local", "double_buffered"),
    ("sharded", "double_buffered"),
    ("multipod", "double_buffered"),
])
def test_placement_smoke_with_save_restore(placement, ingestion,
                                           tmp_path):
    config = ServiceConfig(
        batch_size=B, n_pad=N_PAD, k_pad=K_PAD,
        placement=placement, ingestion=ingestion,
        topk=TopKSpec(k=2),
        checkpoint=CheckpointPolicy(directory=str(tmp_path)))
    ticks = _ticks()

    # uninterrupted reference run
    with FingerService.open(config, _graphs(),
                            mesh=_mesh_for(placement)) as svc:
        ref = []
        for d in ticks:
            svc.ingest(d)
            report = svc.poll()
            assert report is not None
            ref.append(svc.scores())
        vals, ids = svc.top_anomalies(2)
        assert vals.shape == (2,) and ids.shape == (2,)
        assert vals[0] >= vals[1] >= 0.0
        order = np.argsort(ref[-1])[::-1][:2]
        np.testing.assert_array_equal(ids, order)
        if placement == "multipod":
            pv, pi = svc.top_anomalies(2, per_pod=True)
            assert pv.shape == (1, 2)  # 1 pod on the host mesh
            np.testing.assert_array_equal(pi[0], order)

    # save mid-run, then restore into a fresh service and resume
    with FingerService.open(config, _graphs(),
                            mesh=_mesh_for(placement)) as svc:
        for d in ticks[:2]:
            svc.ingest(d)
            svc.poll()
        svc.save()
        assert svc.step == 2

    resumed = FingerService.restore(config, mesh=_mesh_for(placement))
    assert resumed.step == 2
    for t, d in enumerate(ticks[2:], start=2):
        resumed.ingest(d)
        resumed.poll()
        np.testing.assert_array_equal(resumed.scores(), ref[t])
    resumed.close()
