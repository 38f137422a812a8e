"""Logical-axis sharding: one vocabulary of named axes, one place that
maps them onto the physical mesh (MaxText-style).

Parallelism encoded here (DESIGN.md §6):
  DP   : "batch"  → ("pod", "data")      activation batch axis
  FSDP : "embed"  → "data"               params sharded at rest, gathered
                                         just-in-time inside the layer scan
  TP   : "heads"/"ff"/"vocab" → "model"  Megatron column/row splits
  EP   : "experts" → "model"             expert parallelism for MoE
  SP   : "seq_kv" → "model"              sequence-sharded KV (flash-decode)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def auto_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis `AxisType.Auto`.

    `jax.make_mesh` makes Explicit axes by default, which type-check
    every sharded array op: the serving top-k merge and the sharded
    ticks rely on the compiler choosing shardings (a (p·k,) candidate
    row does not split evenly over p shards), so their meshes are Auto.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical axis name → physical mesh axis (or axes tuple, or None)."""

    batch: Tuple[str, ...] = ("data",)
    fsdp: object = "data"  # str, tuple of axes (HSDP across pods), or None
    tensor: Optional[str] = "model"
    tp_size: int = 1  # size of the tensor axis (for divisibility checks)
    # batch=1 long-context decode: the data axis is idle for activations,
    # so the sequence-sharded KV cache spreads over (data, model) instead
    # of model alone (flash-decode over 256 ways instead of 16).
    seq_kv_over_data: bool = False

    def spec_for(self, logical_axes: Tuple[Optional[str], ...]) -> P:
        out = []
        for ax in logical_axes:
            if ax is None:
                out.append(None)
            elif ax == "batch":
                if not self.batch:
                    out.append(None)  # replicated batch (e.g. batch=1 cells)
                else:
                    out.append(self.batch if len(self.batch) > 1
                               else self.batch[0])
            elif ax in ("embed", "ff_data"):
                out.append(self.fsdp)
            elif ax == "seq_kv":
                if self.seq_kv_over_data and self.fsdp:
                    fs = self.fsdp if isinstance(self.fsdp, tuple) \
                        else (self.fsdp,)
                    out.append(fs + (self.tensor,))
                else:
                    out.append(self.tensor)
            elif ax in ("heads", "kv_heads", "ff", "vocab", "experts",
                        "d_inner"):
                out.append(self.tensor)
            elif ax in ("replicated", "layers"):
                out.append(None)
            else:
                raise ValueError(f"unknown logical axis {ax!r}")
        return P(*out)


# Rules used when no mesh is active (single-device smoke tests).
NO_SHARDING = ShardingRules(batch=("data",), fsdp=None, tensor=None, tp_size=1)


def single_pod_rules(tp: int = 16) -> ShardingRules:
    return ShardingRules(batch=("data",), fsdp="data", tensor="model",
                         tp_size=tp)


def multi_pod_rules(tp: int = 16) -> ShardingRules:
    # params/optimizer state shard across BOTH pods and the data axis
    # (HSDP): the second pod doubles parameter capacity, at the price of
    # inter-pod all-gathers overlapping the layer compute.
    return ShardingRules(batch=("pod", "data"), fsdp=("pod", "data"),
                         tensor="model", tp_size=tp)


def constrain(x: jax.Array, rules: ShardingRules,
              logical_axes: Tuple[Optional[str], ...]) -> jax.Array:
    """with_sharding_constraint under a mesh; no-op when rules are empty."""
    if rules is None or rules.tp_size == 1 and rules.fsdp is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules.spec_for(logical_axes))


def named_sharding(mesh: Mesh, rules: ShardingRules,
                   logical_axes: Tuple[Optional[str], ...]) -> NamedSharding:
    return NamedSharding(mesh, rules.spec_for(logical_axes))


def pad_to_multiple(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


def padded_vocab(vocab: int, rules: ShardingRules) -> int:
    """Vocab padded to an MXU-friendly multiple that also shards over TP.

    Padded logit rows are masked to -inf before softmax/loss, so the
    padding is numerically invisible (standard MaxText/Megatron practice).
    """
    tp = rules.tp_size if rules and rules.tensor else 1
    mult = 128 * tp // __import__("math").gcd(128, tp)
    return pad_to_multiple(vocab, mult)


def effective_heads(n_heads: int, rules: ShardingRules) -> int:
    """Q heads padded up to the TP degree so the head axis always shards.

    Padded heads are exact no-ops: their W_o rows are zero-initialized and
    their outputs are discarded by construction. The padding waste is
    deliberately visible in the roofline useful-FLOPs ratio.
    """
    tp = rules.tp_size if rules and rules.tensor else 1
    if tp <= 1 or n_heads % tp == 0:
        return n_heads
    return pad_to_multiple(n_heads, tp)


def kv_heads_shardable(n_kv: int, rules: ShardingRules) -> bool:
    tp = rules.tp_size if rules and rules.tensor else 1
    return tp > 1 and n_kv % tp == 0
