"""Graph representations used across the FINGER framework.

Three interchangeable representations, all registered as JAX pytrees so
they can flow through jit / scan / shard_map:

- ``DenseGraph``  : (n, n) symmetric weight matrix. The natural format for
  Hi-C contact maps and the exact-VNGE oracle.
- ``EdgeList``    : padded COO with an explicit validity mask. The natural
  format for streaming graphs and O(n + m) FINGER computation.
- ``GraphDelta``  : a padded set of undirected edge-weight changes
  (additions, deletions = negative deltas, re-weights), the unit of the
  paper's incremental setting (Theorem 2).

All graphs are undirected with nonnegative weights; every undirected edge
(i, j), i < j, is stored exactly once in EdgeList/GraphDelta.

Mask-aware node layout
----------------------
The node dimension is a *layout* size (``n_nodes``, aliased ``n_pad``):
a static pytree field shared by every stream stacked into one batch.
The layout itself is a first-class object — `repro.graphs.layout
.NodeLayout` — which owns the constructor-argument resolution and the
mask-embedding logic below, plus the grow/compact migration lifecycle
(every constructor here accepts ``layout=`` in place of ``n_pad=``).
Which of those slots are real is the per-stream dynamic ``node_mask``
((n,) 0/1, ``None`` meaning "all active"). Padding with inactive nodes
is exact for every FINGER statistic: an isolated node has zero strength,
contributes zero to S, Σs², Σ_E w² and s_max, and adds only a zero
eigenvalue to L_N (0 ln 0 = 0), so H, Ĥ and H̃ are all invariant — the
robustness-to-isolated-nodes property that quadratic-approximation work
(Choi et al., arXiv:1811.11087) leans on. That is what lets streams with
distinct true node counts share one compiled (B, n_pad) program.

Node joins/leaves are first-class deltas: ``GraphDelta`` carries optional
``node_ids``/``node_flag`` slots (+1 join, -1 leave, 0 padding). Joins
activate a node *before* the delta's edge changes (so a join + its first
edges fit in one delta); leaves deactivate *after* them (so edge
deletions + the leave fit in one delta). A leave requires the node to be
isolated once the delta's edge changes have applied — deactivating a
node that still has incident weight leaves its stale contribution in the
scalar statistics (same contract class as ``w_old`` correctness).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.layout import NodeLayout


def _drop_self_loops(senders: np.ndarray, receivers: np.ndarray,
                     *payloads: np.ndarray, kind: str):
    """Drop i == j slots host-side (Lemma 1 assumes a zero diagonal).

    A self-loop slot would double-count into the node strength while
    never appearing as an off-diagonal Laplacian entry, silently skewing
    Q, s_max, and every incremental statistic downstream.
    """
    loops = senders == receivers
    if not loops.any():
        return (senders, receivers, *payloads)
    warnings.warn(
        f"{kind}: dropping {int(loops.sum())} self-loop slot(s) "
        "(i == j); Lemma 1 assumes a zero diagonal",
        stacklevel=3,
    )
    keep = ~loops
    return (senders[keep], receivers[keep],
            *(p[keep] for p in payloads))


def _pytree_dataclass(cls=None, *, static_fields=()):
    """Minimal frozen-dataclass pytree registration helper."""

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        fields = [f.name for f in dataclasses.fields(c)]
        data_fields = [f for f in fields if f not in static_fields]

        def flatten(obj):
            children = tuple(getattr(obj, f) for f in data_fields)
            aux = tuple(getattr(obj, f) for f in static_fields)
            return children, aux

        def unflatten(aux, children):
            kwargs = dict(zip(data_fields, children))
            kwargs.update(dict(zip(static_fields, aux)))
            return c(**kwargs)

        jax.tree_util.register_pytree_node(c, flatten, unflatten)
        return c

    if cls is None:
        return wrap
    return wrap(cls)


def _resolve_layout_args(n_nodes: int, n_pad, node_mask, layout, kind: str):
    """Constructor args → (layout size, mask) via `NodeLayout.resolve`.

    The legacy unmasked layout (nothing supplied) keeps layout size =
    n_nodes and mask None; everything else is owned by `NodeLayout`.
    """
    resolved, mask = NodeLayout.resolve(n_nodes, n_pad, node_mask,
                                        layout=layout, kind=kind)
    if resolved is None:
        return int(n_nodes), None
    return resolved.n_pad, mask


@_pytree_dataclass(static_fields=("n_nodes",))
class DenseGraph:
    """Symmetric dense weighted adjacency. ``weights[i, j] == weights[j, i]``.

    ``n_nodes`` is the layout size (``n_pad``); ``node_mask`` (optional,
    (n,) 0/1) marks which slots hold real nodes. Inactive rows/columns of
    ``weights`` are zero by construction.
    """

    weights: jax.Array  # (n, n), nonnegative, zero diagonal
    n_nodes: int
    node_mask: Optional[jax.Array] = None  # (n,) 0/1; None = all active

    @property
    def n(self) -> int:
        return self.n_nodes

    @property
    def n_pad(self) -> int:
        return self.n_nodes

    @property
    def layout(self) -> NodeLayout:
        """This graph's node layout (host graphs are generation 0)."""
        return NodeLayout(self.n_nodes)

    def n_active(self) -> jax.Array:
        if self.node_mask is None:
            return jnp.asarray(self.n_nodes, jnp.int32)
        return jnp.sum(self.node_mask).astype(jnp.int32)

    def masked_weights(self) -> jax.Array:
        """W with inactive rows/columns forced to exactly zero."""
        if self.node_mask is None:
            return self.weights
        m = self.node_mask.astype(self.weights.dtype)
        return self.weights * m[:, None] * m[None, :]

    def strengths(self) -> jax.Array:
        return jnp.sum(self.masked_weights(), axis=1)

    def pad_to(self, n_pad: Union[int, NodeLayout]) -> "DenseGraph":
        """Embed into an n_pad (or NodeLayout) layout; new slots are
        inactive (mask 0).

        Always returns a graph *with* a node mask (all-ones when nothing
        was padded) so heterogeneous batches share one pytree structure.
        """
        layout = n_pad if isinstance(n_pad, NodeLayout) \
            else NodeLayout(int(n_pad))
        n = self.n_nodes
        if layout.n_pad < n:
            raise ValueError(f"pad_to: n_pad={layout.n_pad} < n_nodes={n}")
        mask = layout.embed_mask(self.node_mask, n,
                                 dtype=self.weights.dtype)
        w = self.weights
        if layout.n_pad > n:
            w = jnp.pad(w, ((0, layout.n_pad - n), (0, layout.n_pad - n)))
        return DenseGraph(weights=w, n_nodes=layout.n_pad, node_mask=mask)

    @staticmethod
    def from_weights(w: jax.Array, n_pad: Optional[int] = None,
                     node_mask: Optional[jax.Array] = None,
                     layout: Optional[NodeLayout] = None) -> "DenseGraph":
        n = w.shape[0]
        w = 0.5 * (w + w.T)
        w = w * (1.0 - jnp.eye(n, dtype=w.dtype))
        if n_pad is None and node_mask is None and layout is None:
            return DenseGraph(weights=w, n_nodes=n)
        n_layout, node_mask = _resolve_layout_args(
            n, n_pad, node_mask, layout, kind="DenseGraph.from_weights")
        node_mask = node_mask.astype(w.dtype)
        if n_layout > n:
            w = jnp.pad(w, ((0, n_layout - n), (0, n_layout - n)))
        w = w * node_mask[:, None] * node_mask[None, :]
        return DenseGraph(weights=w, n_nodes=n_layout, node_mask=node_mask)


@_pytree_dataclass(static_fields=("n_nodes",))
class EdgeList:
    """Padded undirected edge list. Invalid (padding) slots have mask 0.

    ``senders[k] < receivers[k]`` for valid slots; each undirected edge
    appears exactly once. ``n_nodes`` is the layout size; ``node_mask``
    (optional) marks active node slots, and edges touching an inactive
    node contribute exactly zero to every statistic.
    """

    senders: jax.Array  # (m_pad,) int32
    receivers: jax.Array  # (m_pad,) int32
    weights: jax.Array  # (m_pad,) float
    mask: jax.Array  # (m_pad,) float 0/1
    n_nodes: int
    node_mask: Optional[jax.Array] = None  # (n,) 0/1; None = all active

    @property
    def n(self) -> int:
        return self.n_nodes

    @property
    def n_pad(self) -> int:
        return self.n_nodes

    @property
    def layout(self) -> NodeLayout:
        """This graph's node layout (host graphs are generation 0)."""
        return NodeLayout(self.n_nodes)

    @property
    def m_pad(self) -> int:
        return self.senders.shape[0]

    def n_active(self) -> jax.Array:
        if self.node_mask is None:
            return jnp.asarray(self.n_nodes, jnp.int32)
        return jnp.sum(self.node_mask).astype(jnp.int32)

    def n_edges(self) -> jax.Array:
        return jnp.sum(self.mask).astype(jnp.int32)

    def masked_weights(self) -> jax.Array:
        w = self.weights * self.mask
        if self.node_mask is not None:
            nm = self.node_mask
            w = w * nm[self.senders] * nm[self.receivers]
        return w

    def strengths(self) -> jax.Array:
        w = self.masked_weights()
        s = jnp.zeros((self.n_nodes,), dtype=self.weights.dtype)
        s = s.at[self.senders].add(w, mode="drop")
        s = s.at[self.receivers].add(w, mode="drop")
        if self.node_mask is not None:
            s = s * self.node_mask
        return s

    def pad_to(self, n_pad: Union[int, NodeLayout]) -> "EdgeList":
        """Embed into an n_pad (or NodeLayout) node layout (edge arrays
        unchanged)."""
        layout = n_pad if isinstance(n_pad, NodeLayout) \
            else NodeLayout(int(n_pad))
        n = self.n_nodes
        if layout.n_pad < n:
            raise ValueError(f"pad_to: n_pad={layout.n_pad} < n_nodes={n}")
        mask = layout.embed_mask(self.node_mask, n,
                                 dtype=self.weights.dtype)
        return EdgeList(senders=self.senders, receivers=self.receivers,
                        weights=self.weights, mask=self.mask,
                        n_nodes=layout.n_pad, node_mask=mask)

    def to_dense(self) -> DenseGraph:
        w = self.masked_weights()
        a = jnp.zeros((self.n_nodes, self.n_nodes), dtype=self.weights.dtype)
        a = a.at[self.senders, self.receivers].add(w, mode="drop")
        a = a.at[self.receivers, self.senders].add(w, mode="drop")
        return DenseGraph(weights=a, n_nodes=self.n_nodes,
                          node_mask=self.node_mask)

    @staticmethod
    def from_dense(g: DenseGraph, m_pad: Optional[int] = None) -> "EdgeList":
        """Host-side conversion (uses numpy; not jit-able)."""
        w = np.asarray(g.masked_weights())
        iu, ju = np.triu_indices(g.n_nodes, k=1)
        vals = w[iu, ju]
        nz = vals != 0.0
        iu, ju, vals = iu[nz], ju[nz], vals[nz]
        m = len(vals)
        if m_pad is None:
            m_pad = max(int(m), 1)
        if m > m_pad:
            raise ValueError(f"m={m} exceeds m_pad={m_pad}")
        pad = m_pad - m
        return EdgeList(
            senders=jnp.asarray(np.concatenate([iu, np.zeros(pad, np.int32)]), jnp.int32),
            receivers=jnp.asarray(np.concatenate([ju, np.zeros(pad, np.int32)]), jnp.int32),
            weights=jnp.asarray(np.concatenate([vals, np.zeros(pad)]), jnp.float32),
            mask=jnp.asarray(np.concatenate([np.ones(m), np.zeros(pad)]), jnp.float32),
            n_nodes=g.n_nodes,
            node_mask=g.node_mask,
        )

    @staticmethod
    def from_arrays(senders, receivers, weights, n_nodes: int,
                    m_pad: Optional[int] = None,
                    n_pad: Optional[int] = None,
                    node_mask: Optional[jax.Array] = None,
                    layout: Optional[NodeLayout] = None) -> "EdgeList":
        senders = np.asarray(senders, np.int32)
        receivers = np.asarray(receivers, np.int32)
        weights = np.asarray(weights, np.float32)
        senders, receivers, weights = _drop_self_loops(
            senders, receivers, weights, kind="EdgeList.from_arrays")
        lo = np.minimum(senders, receivers)
        hi = np.maximum(senders, receivers)
        senders, receivers = lo, hi
        m = len(senders)
        if m_pad is None:
            m_pad = max(m, 1)
        pad = m_pad - m
        n_layout, node_mask = _resolve_layout_args(
            n_nodes, n_pad, node_mask, layout, kind="EdgeList.from_arrays")
        return EdgeList(
            senders=jnp.asarray(np.concatenate([senders, np.zeros(pad, np.int32)])),
            receivers=jnp.asarray(np.concatenate([receivers, np.zeros(pad, np.int32)])),
            weights=jnp.asarray(np.concatenate([weights, np.zeros(pad, np.float32)])),
            mask=jnp.asarray(np.concatenate([np.ones(m, np.float32),
                                             np.zeros(pad, np.float32)])),
            n_nodes=n_layout,
            node_mask=node_mask,
        )


@_pytree_dataclass(static_fields=("n_nodes", "layout_generation"))
class GraphDelta:
    """Padded set of undirected edge-weight deltas (Theorem 2's ΔG).

    ``dw[k]`` is the signed weight change of edge (senders[k], receivers[k]).
    Edge addition: dw = +w; deletion: dw = -w_old; re-weight: dw = w_new - w_old.
    ``w_old[k]`` is the edge's weight in G *before* the delta (0 for additions);
    carrying it makes the Theorem-2 ΔQ computable in O(Δm) without touching W.

    Node joins/leaves ride along in the optional ``node_ids``/``node_flag``
    slots (+1 join, -1 leave, 0 padding; see the module docstring for the
    join-before-edges / leave-after-edges ordering and the isolated-leave
    contract). Joins of isolated nodes change no FINGER statistic, so a
    node-only delta is a zero-cost mask update.

    ``layout_generation`` (optional) names the *migration generation* of
    the `NodeLayout` the delta is addressed in — stamped by passing
    ``layout=`` to `from_arrays`. A raw delta only carries a layout
    *size* (``n_nodes``), which is ambiguous across size-reusing
    migration chains (grow 128, compact to 96, grow back to 128: two
    distinct layouts of size 128); the generation makes the serving
    ingestion remap exact — a generation-stamped delta is renumbered
    through precisely the migrations since *its* layout, or rejected by
    name when that chain is unknown. Ingestion strips the field before
    anything reaches a compiled tick, so it never fragments the jit
    cache.

    ``edge_slots`` (optional) is the sparse-path edge-store addressing:
    for a delta already translated into *slot space* by a
    `repro.core.sparse.SlotMap`, ``edge_slots[k]`` names the slot of
    edge k in the stream's padded (m_pad,) edge-weight store (the
    `EDGE_SLOT_SENTINEL` value on padding/gated lanes, which every
    ``mode="drop"`` scatter ignores). Dense-path deltas leave it None.
    """

    senders: jax.Array  # (k_pad,) int32
    receivers: jax.Array  # (k_pad,) int32
    dw: jax.Array  # (k_pad,) float
    w_old: jax.Array  # (k_pad,) float
    mask: jax.Array  # (k_pad,) float 0/1
    n_nodes: int
    node_ids: Optional[jax.Array] = None  # (j_pad,) int32
    node_flag: Optional[jax.Array] = None  # (j_pad,) float +1/-1/0
    layout_generation: Optional[int] = None  # static; None = unstamped
    edge_slots: Optional[jax.Array] = None  # (k_pad,) int32; sparse only

    @property
    def n(self) -> int:
        return self.n_nodes

    @property
    def n_pad(self) -> int:
        return self.n_nodes

    @property
    def layout(self) -> NodeLayout:
        """The node layout this delta is addressed in (generation 0
        when unstamped — a raw delta carries no migration history)."""
        return NodeLayout(self.n_nodes,
                          generation=self.layout_generation or 0)

    @property
    def has_node_slots(self) -> bool:
        return self.node_ids is not None

    def lane_count(self) -> int:
        """Edge lanes the mask keeps, counted on the host (a device
        mask is read back)."""
        return int(np.count_nonzero(np.asarray(self.mask)))

    def scaled(self, factor: float) -> "GraphDelta":
        """ΔG/2 for Algorithm 2 (the averaged graph G ⊕ ΔG/2).

        Joins are kept (a joining node exists in Ḡ, isolated or with its
        half-weight first edges) but leaves are dropped: a node leaving
        G' is still present in Ḡ with its half-weight edges, so Ḡ must
        not deactivate it.
        """
        flag = self.node_flag
        if flag is not None:
            flag = jnp.maximum(flag, 0.0)
        return GraphDelta(
            senders=self.senders, receivers=self.receivers,
            dw=self.dw * factor, w_old=self.w_old, mask=self.mask,
            n_nodes=self.n_nodes, node_ids=self.node_ids, node_flag=flag,
            layout_generation=self.layout_generation,
            edge_slots=self.edge_slots,
        )

    def delta_strengths(self, n: Optional[int] = None) -> jax.Array:
        """Δs_i for all nodes (dense (n,) scatter; zero off ΔV)."""
        if n is None:
            n = self.n_nodes
        dwm = self.dw * self.mask
        ds = jnp.zeros((n,), dtype=self.dw.dtype)
        ds = ds.at[self.senders].add(dwm, mode="drop")
        ds = ds.at[self.receivers].add(dwm, mode="drop")
        return ds

    def delta_s_total(self) -> jax.Array:
        """ΔS = Σ_i Δs_i = 2 Σ_E Δw."""
        return 2.0 * jnp.sum(self.dw * self.mask)

    @staticmethod
    def from_arrays(senders, receivers, dw, w_old, n_nodes: int,
                    k_pad: Optional[int] = None,
                    n_pad: Optional[int] = None,
                    join=(), leave=(),
                    j_pad: Optional[int] = None,
                    layout: Optional[NodeLayout] = None) -> "GraphDelta":
        """A padded delta with device leaves: `host_from_arrays`, then
        one `jnp.asarray` per leaf."""
        host = GraphDelta.host_from_arrays(
            senders, receivers, dw, w_old, n_nodes, k_pad=k_pad,
            n_pad=n_pad, join=join, leave=leave, j_pad=j_pad,
            layout=layout)
        return jax.tree_util.tree_map(jnp.asarray, host)

    @staticmethod
    def host_from_arrays(senders, receivers, dw, w_old, n_nodes: int,
                         k_pad: Optional[int] = None,
                         n_pad: Optional[int] = None,
                         join=(), leave=(),
                         j_pad: Optional[int] = None,
                         layout: Optional[NodeLayout] = None
                         ) -> "GraphDelta":
        """The padded delta with host (numpy) leaves, nothing put on the
        device: self-loops dropped, lanes ordered (lo, hi) and padded to
        ``k_pad``, joins then leaves padded to ``j_pad``, each bound
        checked (`ValueError`). For a delta whose next reader is host
        code, such as a sparse shard's `SlotMap`."""
        senders = np.asarray(senders, np.int32)
        receivers = np.asarray(receivers, np.int32)
        dw = np.asarray(dw, np.float32)
        w_old = np.asarray(w_old, np.float32)
        senders, receivers, dw, w_old = _drop_self_loops(
            senders, receivers, dw, w_old, kind="GraphDelta.from_arrays")
        lo = np.minimum(senders, receivers)
        hi = np.maximum(senders, receivers)
        k = len(senders)
        if k_pad is None:
            k_pad = max(k, 1)
        if k > k_pad:
            raise ValueError(f"k={k} delta edges exceed k_pad={k_pad}")
        pad = k_pad - k
        z = np.zeros(pad, np.float32)
        if layout is not None:
            if n_pad is not None and int(n_pad) != layout.n_pad:
                raise ValueError(
                    f"GraphDelta.from_arrays: n_pad={n_pad} conflicts "
                    f"with layout.n_pad={layout.n_pad}")
            n_pad = layout.n_pad
        n_layout = int(n_nodes) if n_pad is None else int(n_pad)
        if n_layout < n_nodes:
            raise ValueError(
                f"GraphDelta.from_arrays: n_pad={n_layout} < "
                f"n_nodes={n_nodes}")
        node_ids = node_flag = None
        join = np.asarray(join, np.int32).ravel()
        leave = np.asarray(leave, np.int32).ravel()
        for name, ids in (("join", join), ("leave", leave)):
            if ids.size and (ids.min() < 0 or ids.max() >= n_layout):
                # The jit-side scatters use mode="drop", which would
                # silently ignore an out-of-layout node — a tenant
                # outgrowing n_pad must be a hard error instead.
                raise ValueError(
                    f"GraphDelta.from_arrays: {name} node id(s) "
                    f"{sorted(set(int(i) for i in ids if i < 0 or i >= n_layout))} "
                    f"outside the n_pad={n_layout} layout; re-pad the "
                    "stream to a larger n_pad to grow past it")
        if join.size or leave.size or j_pad is not None:
            j = int(join.size + leave.size)
            if j_pad is None:
                j_pad = max(j, 1)
            if j > j_pad:
                raise ValueError(
                    f"{j} node join/leave slots exceed j_pad={j_pad}")
            jpad = j_pad - j
            node_ids = np.concatenate(
                [join, leave, np.zeros(jpad, np.int32)])
            node_flag = np.concatenate(
                [np.ones(join.size, np.float32),
                 -np.ones(leave.size, np.float32),
                 np.zeros(jpad, np.float32)])
        return GraphDelta(
            senders=np.concatenate([lo, np.zeros(pad, np.int32)]),
            receivers=np.concatenate([hi, np.zeros(pad, np.int32)]),
            dw=np.concatenate([dw, z]),
            w_old=np.concatenate([w_old, z]),
            mask=np.concatenate([np.ones(k, np.float32), z]),
            n_nodes=n_layout,
            node_ids=node_ids,
            node_flag=node_flag,
            layout_generation=None if layout is None else layout.generation,
        )


def node_mask_after_joins(node_mask: jax.Array,
                          delta: GraphDelta) -> jax.Array:
    """Activate the delta's join slots (flag > 0); no-op on others."""
    join = (delta.node_flag > 0).astype(node_mask.dtype)
    return node_mask.at[delta.node_ids].max(join, mode="drop")


def node_mask_after_leaves(node_mask: jax.Array,
                           delta: GraphDelta) -> jax.Array:
    """Deactivate the delta's leave slots (flag < 0); no-op on others."""
    stay = 1.0 - (delta.node_flag < 0).astype(node_mask.dtype)
    return node_mask.at[delta.node_ids].min(stay, mode="drop")


def gate_delta_by_nodes(delta: GraphDelta,
                        node_mask: jax.Array) -> GraphDelta:
    """Zero the validity of delta edges touching an inactive node.

    The gate uses the *post-join* mask so a join plus its first edges
    can share one delta; it is what makes padded node slots contribute
    exactly zero even if a stray delta edge points into the padding.
    """
    gate = node_mask[delta.senders] * node_mask[delta.receivers]
    return GraphDelta(
        senders=delta.senders, receivers=delta.receivers,
        dw=delta.dw, w_old=delta.w_old,
        mask=delta.mask * gate.astype(delta.mask.dtype),
        n_nodes=delta.n_nodes,
        node_ids=delta.node_ids, node_flag=delta.node_flag,
        layout_generation=delta.layout_generation,
        edge_slots=delta.edge_slots,
    )


def apply_delta_dense(g: DenseGraph, delta: GraphDelta) -> DenseGraph:
    """G' = G ⊕ ΔG on the dense representation (oracle path).

    Mirrors the incremental semantics: joins activate before the edge
    changes, edges are gated by the post-join mask, leaves deactivate
    after them (zeroing the left nodes' rows/columns — a no-op under the
    isolated-leave contract).
    """
    mask = g.node_mask
    if delta.has_node_slots and mask is None:
        mask = jnp.ones((g.n_nodes,), g.weights.dtype)
    if delta.has_node_slots:
        mask = node_mask_after_joins(mask, delta)
    if mask is not None:
        delta = gate_delta_by_nodes(delta, mask)
    dwm = delta.dw * delta.mask
    w = g.weights
    w = w.at[delta.senders, delta.receivers].add(dwm, mode="drop")
    w = w.at[delta.receivers, delta.senders].add(dwm, mode="drop")
    if delta.has_node_slots:
        mask = node_mask_after_leaves(mask, delta)
    if mask is not None:
        w = w * mask[:, None] * mask[None, :]
    return DenseGraph(weights=w, n_nodes=g.n_nodes, node_mask=mask)
