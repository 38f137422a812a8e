"""Plain reference of the served score: FINGER's incremental
Jensen-Shannon distance (the paper's Algorithm 2), graph by graph.

Each tick the reference recomputes the three entropies from the
graph's own statistics: the strengths, their sum S, sum of squares,
and the sum of squared edge weights, over G, the average graph
G + dG/2 and G + dG. It shares no code with the program and applies
no incremental identity (Theorem 2): the program must agree with a
from-scratch evaluation of the same quantities.

    Q     = 1 - (sum_i s_i^2 + 2 sum_E w^2) / S^2         (Lemma 1)
    H~(G) = -Q ln(2 s_max / S), 0 for an empty graph        (eq. 2)
    JS    = sqrt(max(H~(Gbar) - (H~(G) + H~(G')) / 2, 0))

``s_max`` follows the paper's eq. (3), the rule the configurations
state (``exact_smax: false``): it rises to the largest new strength of
a touched node and never falls, except that an emptied graph resets it.

``dtype`` is the precision of every array and scalar: float64 for the
reference; bfloat16 (through ``ml_dtypes``) makes the lower-precision
control.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


class FingerJS:
    """One tenant's graph statistics and its score per tick."""

    def __init__(self, n_nodes: int, lo: np.ndarray, hi: np.ndarray,
                 weights: np.ndarray, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        w = np.asarray(weights, self.dtype)
        s = np.zeros(n_nodes, self.dtype)
        np.add.at(s, lo, w)
        np.add.at(s, hi, w)
        self.s = s
        self.sum_w2 = np.sum(w * w, dtype=self.dtype)
        self.s_max = s.max() if n_nodes else self.dtype.type(0)

    def _entropy(self, s: np.ndarray, sum_w2, s_max):
        d = self.dtype.type
        total = np.sum(s, dtype=self.dtype)
        if not total > 0:
            return d(0)
        q = d(1) - (np.sum(s * s, dtype=self.dtype) + d(2) * sum_w2) \
            / (total * total)
        return -q * np.log(d(2) * s_max / total)

    def _after(self, lo, hi, dw, w_old, scale):
        d = self.dtype.type
        step = np.asarray(dw, self.dtype) * d(scale)
        old = np.asarray(w_old, self.dtype)
        s = self.s.copy()
        np.add.at(s, lo, step)
        np.add.at(s, hi, step)
        new = old + step
        sum_w2 = self.sum_w2 + np.sum(new * new - old * old,
                                      dtype=self.dtype)
        touched = np.concatenate([lo, hi])
        s_max = max(self.s_max, s[touched].max()) if touched.size \
            else self.s_max
        if not np.sum(s, dtype=self.dtype) > 0:
            s_max = d(0)
        return s, sum_w2, s_max

    def step(self, lo, hi, dw, w_old) -> float:
        """Apply one tick's lanes; return JS(G, G + dG)."""
        h_g = self._entropy(self.s, self.sum_w2, self.s_max)
        half = self._after(lo, hi, dw, w_old, 0.5)
        full = self._after(lo, hi, dw, w_old, 1.0)
        div = self._entropy(*half) - (h_g + self._entropy(*full)) \
            / self.dtype.type(2)
        self.s, self.sum_w2, self.s_max = full
        return float(np.sqrt(max(float(div), 0.0)))

    def stats(self) -> Sequence[float]:
        """The carried (Q, S, s_max) of the current graph."""
        d = self.dtype.type
        total = np.sum(self.s, dtype=self.dtype)
        q = d(1) - (np.sum(self.s * self.s, dtype=self.dtype)
                    + d(2) * self.sum_w2) / (total * total) \
            if total > 0 else d(1)
        return float(q), float(total), float(self.s_max)
