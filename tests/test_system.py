"""End-to-end behaviour: the paper's three application tasks on the
synthesized datasets."""
import numpy as np
import pytest


from repro.baselines import veo_score
from repro.core import finger_state, jsdist_fast, jsdist_incremental
from repro.graphs.generators import barabasi_albert, barabasi_albert_edges
from repro.graphs.streams import (
    churn_stream,
    dos_attack_edge_sequence,
    dos_attack_sequence,
    hic_bifurcation_sequence,
)


class TestDosDetection:
    """Paper Table 3: the planted DoS transition gets the top JS score."""

    def test_finger_detects_dos(self):
        hits = 0
        trials = 6
        for seed in range(trials):
            seq, attack_at = dos_attack_sequence(n=250, attack_frac=0.05,
                                                 seed=seed)
            scores = [float(jsdist_fast(seq.graphs[t], seq.graphs[t + 1],
                                        power_iters=50))
                      for t in range(len(seq.graphs) - 1)]
            top2 = np.argsort(scores)[-2:]
            hits += int(attack_at in top2)
        assert hits >= trials - 1, f"detected {hits}/{trials}"

    def test_incremental_agrees_with_fast(self):
        seq, attack_at = dos_attack_sequence(n=200, attack_frac=0.08, seed=3)
        st = finger_state(seq.graphs[0])
        inc_scores = []
        for d in seq.deltas:
            dist, st = jsdist_incremental(st, d, exact_smax=True)
            inc_scores.append(float(dist))
        assert int(np.argmax(inc_scores)) == attack_at

    @pytest.mark.parametrize("n,m,seed", [(40, 3, 0), (120, 2, 5)])
    def test_ba_edge_list_is_the_dense_generator(self, n, m, seed):
        lo, hi = barabasi_albert_edges(n, m, seed)
        w = np.zeros((n, n))
        w[lo, hi] = w[hi, lo] = 1.0
        assert np.all(lo < hi)
        np.testing.assert_array_equal(
            w, np.asarray(barabasi_albert(n, m, seed).weights))

    def test_edge_sequence_replays_and_plants_one_fan_in(self):
        n, frac = 400, 0.05
        first, deltas, attack_at = dos_attack_edge_sequence(
            n=n, n_graphs=8, attack_frac=frac, seed=2)
        valid = np.asarray(first.mask) > 0
        edges = set(zip(np.asarray(first.senders)[valid].tolist(),
                        np.asarray(first.receivers)[valid].tolist()))
        m0 = len(edges)
        for t, d in enumerate(deltas):
            live = np.asarray(d.mask) > 0
            pairs = list(zip(np.asarray(d.senders)[live].tolist(),
                             np.asarray(d.receivers)[live].tolist()))
            dw = np.asarray(d.dw)[live]
            w_old = np.asarray(d.w_old)[live]
            assert len(set(pairs)) == len(pairs)
            for pair, step, old in zip(pairs, dw, w_old):
                assert old == float(pair in edges)
                assert step == (-1.0 if old else 1.0)
                (edges.discard if old else edges.add)(pair)
            born = [p for p, step in zip(pairs, dw) if step > 0]
            hub = np.bincount(np.asarray(born).ravel(), minlength=n).max()
            if t == attack_at:
                assert hub >= int(frac * n) - 3  # some fan-in pre-exists
            else:
                assert hub < int(frac * n) // 2
        assert abs(len(edges) - m0) <= int(frac * n)

    def test_edge_sequence_planted_attack_scores_highest(self):
        first, deltas, attack_at = dos_attack_edge_sequence(
            n=600, n_graphs=7, seed=4)
        st = finger_state(first)
        scores = []
        for d in deltas:
            dist, st = jsdist_incremental(st, d)
            scores.append(float(dist))
        assert int(np.argmax(scores)) == attack_at


class TestBifurcationDetection:
    """Paper Fig. 4: TDS local structure flags the planted bifurcation."""

    def test_finger_tds_peaks_at_bifurcation(self):
        seq = hic_bifurcation_sequence(n=150, bifurcation_at=5, seed=0)
        dists = [float(jsdist_fast(seq.graphs[t], seq.graphs[t + 1],
                                   power_iters=50))
                 for t in range(len(seq.graphs) - 1)]
        # the transition into config B (index 5 -> 6) dominates
        assert int(np.argmax(dists)) == 5

    def test_veo_blind_to_weighted_change(self):
        """The paper's point: VEO is insensitive to edge-weight changes."""
        seq = hic_bifurcation_sequence(n=120, bifurcation_at=5, seed=1)
        veo = [float(veo_score(seq.graphs[t], seq.graphs[t + 1]))
               for t in range(len(seq.graphs) - 1)]
        finger = [float(jsdist_fast(seq.graphs[t], seq.graphs[t + 1],
                                    power_iters=50))
                  for t in range(len(seq.graphs) - 1)]
        # FINGER contrast (peak vs median) far exceeds VEO's
        f_contrast = max(finger) / (np.median(finger) + 1e-12)
        v_contrast = max(veo) / (np.median(veo) + 1e-12)
        assert f_contrast > v_contrast


class TestChurnAnomaly:
    """Wikipedia-style ex-post-facto: JS distance correlates with the
    fraction-of-edges-changed proxy across a bursty churn stream."""

    def test_correlation_with_proxy(self):
        seq = churn_stream(n=150, steps=25, burst_steps=(8, 17),
                           burst_multiplier=12.0, seed=2)
        st = finger_state(seq.graphs[0])
        scores = []
        for d in seq.deltas:
            dist, st = jsdist_incremental(st, d, exact_smax=True)
            scores.append(float(dist))
        proxy = seq.anomaly_truth
        pcc = np.corrcoef(scores, proxy)[0, 1]
        assert pcc > 0.5, f"PCC {pcc}"
        top3 = set(np.argsort(scores)[-3:].tolist())
        assert len({8, 17} & top3) >= 1
