"""Checkpoint pruning policies and restore-latest correctness.

The satellite contract: kill/restart resume must pick the correct
surviving checkpoint after pruning, with *numeric* (not lexicographic)
step ordering in `latest_checkpoint`/`_prune`, and the prune policy is
pluggable (`keep_last`, `keep_every_n`, callable) end to end through
`StreamEngine.save` and `ServiceConfig`.
"""
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.engine import StreamEngine, stack_deltas
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.serving import CheckpointPolicy, FingerService, ServiceConfig
from repro.serving.config import ServiceConfigError, TopKSpec
from repro.serving.checkpoint import (
    latest_checkpoint,
    load_manifest,
    resolve_prune_policy,
    save_checkpoint,
)


def _steps_on_disk(ckpt_dir):
    return sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                  if d.startswith("step_") and "tmp" not in d)


class TestPrunePolicies:
    def test_keep_last_int_and_tuple_agree(self, tmp_path):
        for sub, policy in (("a", 2), ("b", ("keep_last", 2))):
            d = str(tmp_path / sub)
            for step in (1, 2, 3, 4):
                save_checkpoint(d, step, {"x": jnp.zeros(2)},
                                prune_policy=policy)
            assert _steps_on_disk(d) == [3, 4]

    def test_keep_every_n_archives_and_keeps_recovery_window(self, tmp_path):
        d = str(tmp_path)
        for step in range(1, 11):
            save_checkpoint(d, step, {"x": jnp.zeros(2)},
                            prune_policy=("keep_every_n", 5, 2))
        # archive: 5, 10; recovery window: 9, 10
        assert _steps_on_disk(d) == [5, 9, 10]

    def test_callable_policy(self, tmp_path):
        d = str(tmp_path)
        for step in (1, 2, 3, 4, 5):
            save_checkpoint(d, step, {"x": jnp.zeros(2)},
                            prune_policy=lambda steps: [s for s in steps
                                                        if s % 2 == 1])
        assert _steps_on_disk(d) == [1, 3, 5]

    def test_callable_policy_cannot_prune_newest(self, tmp_path):
        """A policy returning nothing still keeps the checkpoint that
        was just written — save must never destroy its own output."""
        d = str(tmp_path)
        for step in (1, 2):
            save_checkpoint(d, step, {"x": jnp.zeros(2)},
                            prune_policy=lambda steps: [])
        assert _steps_on_disk(d) == [2]

    def test_just_written_survives_in_reused_directory(self, tmp_path):
        """A directory left over from an older run with *higher* steps
        must not swallow a new run's first save: the just-written step
        survives pruning even though it is not the numerically newest,
        and becomes latest once the stale steps age out."""
        d = str(tmp_path)
        for step in (4, 5, 6):  # stale previous deployment
            save_checkpoint(d, step, {"x": jnp.zeros(2)}, prune_policy=3)
        save_checkpoint(d, 1, {"x": jnp.ones(2)}, prune_policy=3)
        assert 1 in _steps_on_disk(d)

    def test_legacy_keep_last_kwarg_still_works(self, tmp_path):
        d = str(tmp_path)
        for step in (1, 2, 3):
            save_checkpoint(d, step, {"x": jnp.zeros(2)}, keep_last=1)
        assert _steps_on_disk(d) == [3]

    def test_both_keep_last_and_policy_is_an_error(self, tmp_path):
        with pytest.raises(ValueError, match="not both"):
            save_checkpoint(str(tmp_path), 0, {"x": jnp.zeros(2)},
                            keep_last=1, prune_policy=2)

    def test_malformed_policy_named_error_before_write(self, tmp_path):
        d = str(tmp_path / "nothing_written")
        with pytest.raises(ValueError, match="unknown prune_policy"):
            save_checkpoint(d, 0, {"x": jnp.zeros(2)},
                            prune_policy=("bogus",))
        assert not os.path.isdir(d)

    def test_resolve_rejects_bool_and_nonpositive(self):
        with pytest.raises(ValueError):
            resolve_prune_policy(0)
        with pytest.raises(ValueError):
            resolve_prune_policy(True)
        with pytest.raises(ValueError, match="keep_every_n period"):
            resolve_prune_policy(("keep_every_n", 0, 1))


class TestNumericStepOrdering:
    def test_latest_is_numeric_not_lexicographic(self, tmp_path):
        """step 100000000 overflows the 8-digit zero-pad, so its dirname
        sorts lexicographically *before* step_99999999; numeric parsing
        must still call it the latest."""
        d = str(tmp_path)
        save_checkpoint(d, 99999999, {"x": jnp.zeros(2)}, prune_policy=10)
        save_checkpoint(d, 100000000, {"x": jnp.ones(2)}, prune_policy=10)
        names = sorted(os.listdir(d))
        assert names[0].endswith("100000000")  # lexicographic trap set
        path = latest_checkpoint(d)
        assert load_manifest(path)["step"] == 100000000

    def test_prune_drops_numerically_oldest(self, tmp_path):
        d = str(tmp_path)
        save_checkpoint(d, 99999999, {"x": jnp.zeros(2)}, prune_policy=10)
        save_checkpoint(d, 100000000, {"x": jnp.zeros(2)}, prune_policy=10)
        save_checkpoint(d, 100000001, {"x": jnp.zeros(2)}, prune_policy=2)
        assert _steps_on_disk(d) == [100000000, 100000001]

    def test_mixed_width_dirnames_order_numerically(self, tmp_path):
        """Checkpoints written by an older job with a narrower zero-pad
        must interleave correctly with the current format."""
        d = str(tmp_path)
        save_checkpoint(d, 7, {"x": jnp.zeros(2)}, prune_policy=10)
        os.rename(os.path.join(d, "step_00000007"),
                  os.path.join(d, "step_7"))  # legacy narrow name
        save_checkpoint(d, 100, {"x": jnp.ones(2)}, prune_policy=10)
        path = latest_checkpoint(d)
        assert load_manifest(path)["step"] == 100
        save_checkpoint(d, 101, {"x": jnp.ones(2)}, prune_policy=2)
        assert _steps_on_disk(d) == [100, 101]


class TestRestoreLatestUnderPruning:
    def _serve(self, engine, st, ticks):
        out = []
        for d in ticks:
            scores, st = engine.tick(st, d)
            out.append(np.asarray(scores))
        return out, st

    def _ticks(self, graphs, t, seed=0):
        rng = np.random.default_rng(seed)
        ticks = []
        for _ in range(t):
            ds = []
            for g in graphs:
                n = g.n_nodes
                i, j = sorted(rng.choice(n, 2, replace=False).tolist())
                w_old = float(np.asarray(g.weights)[i, j])
                ds.append(GraphDelta.from_arrays(
                    [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
                    n_nodes=n, k_pad=4))
            ticks.append(stack_deltas(ds))
        return ticks

    def test_resume_picks_surviving_checkpoint_after_pruning(self, tmp_path):
        """Kill/restart drill: save every tick under keep_last=2, kill,
        restore — the resumed scores must continue from the *numerically
        latest surviving* step, bit-exact with the uninterrupted run."""
        graphs = [erdos_renyi(16, 0.2, seed=s, weighted=True)
                  for s in range(4)]
        ticks = self._ticks(graphs, 6)
        engine = StreamEngine()
        ref, _ = self._serve(engine, StreamEngine.init_states(graphs),
                             ticks)

        st = StreamEngine.init_states(graphs)
        for step, d in enumerate(ticks[:4], start=1):
            _, st = engine.tick(st, d)
            engine.save(str(tmp_path), st, step=step, prune_policy=2)
        assert _steps_on_disk(str(tmp_path)) == [3, 4]  # 1, 2 pruned

        fresh = StreamEngine()  # simulated restart
        st2, step = fresh.restore(str(tmp_path))
        assert step == 4
        for t, d in enumerate(ticks[4:], start=4):
            scores, st2 = fresh.tick(st2, d)
            np.testing.assert_array_equal(np.asarray(scores), ref[t])

    def test_service_periodic_save_respects_config_policy(self, tmp_path):
        """ServiceConfig wiring: checkpoint.every_ticks auto-saves with
        the config's prune policy, and FingerService.restore resumes
        from the latest survivor."""
        graphs = [erdos_renyi(16, 0.2, seed=s, weighted=True)
                  for s in range(4)]
        ticks = self._ticks(graphs, 6, seed=3)
        config = ServiceConfig(
            batch_size=4, n_pad=16, k_pad=4, topk=TopKSpec(k=2),
            checkpoint=CheckpointPolicy(directory=str(tmp_path),
                                        prune=("keep_every_n", 4, 1),
                                        every_ticks=2))
        svc = FingerService.open(config, graphs)
        for d in ticks:
            svc.ingest(d)
            svc.poll()
        final = svc.scores()
        svc.close()
        # auto-saved at 2, 4, 6; keep_every_n=4 keeps 4, newest keeps 6
        assert _steps_on_disk(str(tmp_path)) == [4, 6]

        svc2 = FingerService.restore(config)
        assert svc2.step == 6
        np.testing.assert_array_equal(svc2.scores() is None, True)
        # resumed state serves the next tick identically to the live one
        nxt = self._ticks(graphs, 1, seed=99)[0]
        svc2.ingest(nxt)
        ref_engine = StreamEngine()
        ref_states, _ = ref_engine.restore(str(tmp_path))
        ref_scores, _ = ref_engine.tick(ref_states, nxt)
        np.testing.assert_array_equal(np.asarray(svc2.poll().scores),
                                      np.asarray(ref_scores))
        svc2.close()
        assert np.isfinite(final).all()

    def test_bad_config_policy_fails_at_validate(self):
        with pytest.raises(ServiceConfigError, match="prune policy"):
            ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                          checkpoint=CheckpointPolicy(
                              directory="/tmp/x", prune=-1)).validate()
        with pytest.raises(ServiceConfigError, match="every_ticks"):
            ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                          checkpoint=CheckpointPolicy(
                              directory=None,
                              every_ticks=2)).validate()


class TestLayoutGenerationRoundTrip:
    """Satellite contract: checkpoints record their layout generation,
    and `FingerService.restore` walks a checkpoint taken under an older
    layout forward through the directory's migration journal — so one
    checkpoint restores bit-exact onto *both* the generation it was
    saved under and the generation the live service has since migrated
    to (save at n_pad=128, compact() to 96)."""

    B, N0, N_PAD, NEW_N_PAD, K_PAD = 4, 90, 128, 96, 4

    def _tick(self, graphs, seed):
        rng = np.random.default_rng(seed)
        ds = []
        for g in graphs:
            i, j = sorted(rng.choice(self.N0, 2, replace=False).tolist())
            w_old = float(np.asarray(g.weights)[i, j])
            ds.append(GraphDelta.from_arrays(
                [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
                n_nodes=self.N0, n_pad=self.N_PAD, k_pad=self.K_PAD))
        return ds

    def test_restore_across_compaction_both_generations(self, tmp_path):
        import jax

        from repro.serving import FingerService

        graphs = [erdos_renyi(self.N0, 0.05, seed=s, weighted=True)
                  for s in range(self.B)]
        cfg = ServiceConfig(
            batch_size=self.B, n_pad=self.N_PAD, k_pad=self.K_PAD,
            topk=TopKSpec(k=2),
            checkpoint=CheckpointPolicy(directory=str(tmp_path)))
        svc = FingerService.open(cfg, graphs)
        svc.ingest(self._tick(graphs, seed=1))
        svc.poll()
        svc.save()  # generation 0, n_pad=128
        saved = jax.device_get(svc.states())

        report = svc.compact(new_n_pad=self.NEW_N_PAD)
        assert (report.old_n_pad, report.new_n_pad) == (self.N_PAD,
                                                        self.NEW_N_PAD)
        assert report.generation == 1
        live = jax.device_get(svc.states())

        # (a) onto the OLD generation: the checkpoint's own layout.
        svc_old = FingerService.restore(cfg)
        old = jax.device_get(svc_old.states())
        for a, b in zip(jax.tree_util.tree_leaves(saved),
                        jax.tree_util.tree_leaves(old)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert svc_old.layout.generation == 0
        svc_old.close()

        # (b) onto the NEW generation: walked forward through the
        # journaled compaction, bit-exact with the live migrated state.
        svc_new = FingerService.restore(cfg.with_(n_pad=self.NEW_N_PAD))
        new = jax.device_get(svc_new.states())
        assert svc_new.layout.generation == 1
        for a, b in zip(jax.tree_util.tree_leaves(live),
                        jax.tree_util.tree_leaves(new)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # both serve the next tick identically (old-layout deltas are
        # remapped by the restored service's reconstructed grace table)
        nxt = self._tick(graphs, seed=7)
        svc.ingest(nxt)
        svc_new.ingest(nxt)
        np.testing.assert_array_equal(
            np.asarray(svc.poll().scores),
            np.asarray(svc_new.poll().scores))
        svc_new.close()
        svc.close()

    def test_restore_without_migration_chain_is_named_error(self, tmp_path):
        from repro.serving import FingerService

        graphs = [erdos_renyi(8, 0.3, seed=s, weighted=True)
                  for s in range(2)]
        cfg = ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                            topk=TopKSpec(k=1),
                            checkpoint=CheckpointPolicy(str(tmp_path)))
        with FingerService.open(cfg, graphs) as svc:
            svc.save()
        # no layout log at all -> the pre-existing named error
        with pytest.raises(ServiceConfigError, match="layout log"):
            FingerService.restore(cfg.with_(n_pad=16))

    def test_restore_across_grow_via_journal(self, tmp_path):
        """The grow record (index_map=None) also journals: a checkpoint
        saved pre-repad restores onto the grown layout by padding."""
        import jax

        from repro.serving import FingerService

        graphs = [erdos_renyi(8, 0.3, seed=s, weighted=True)
                  for s in range(2)]
        cfg = ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                            topk=TopKSpec(k=1),
                            checkpoint=CheckpointPolicy(str(tmp_path)))
        svc = FingerService.open(cfg, graphs)
        svc.save()
        svc.repad(12)
        live = jax.device_get(svc.states())
        svc_new = FingerService.restore(cfg.with_(n_pad=12))
        assert svc_new.layout.generation == 1
        for a, b in zip(jax.tree_util.tree_leaves(live),
                        jax.tree_util.tree_leaves(
                            jax.device_get(svc_new.states()))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        svc_new.close()
        svc.close()
