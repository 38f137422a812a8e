"""Checkpointing: atomic, content-addressed-by-step, mesh-agnostic.

Arrays are gathered to host, written as one compressed npz keyed by
pytree path, plus a small JSON manifest (step, metadata). Writes are
atomic (tmp dir + rename) so a crash mid-write can never corrupt the
latest checkpoint. Restore re-shards onto whatever mesh the new job runs.

Pruning is a pluggable policy (``prune_policy`` on `save_checkpoint`):

- ``int k`` / ``("keep_last", k)``   : keep the newest k checkpoints.
- ``("keep_every_n", n, k)``         : keep every step divisible by n
  (the long-horizon archive) plus the newest k regardless (the
  crash-recovery window).
- ``callable(steps) -> keep``        : full control; receives the
  ascending list of on-disk step ints, returns those to keep. The
  newest step always survives — a policy can never prune the
  checkpoint that was just written.

All step ordering (pruning and `latest_checkpoint`) is numeric on the
parsed step int, not lexicographic on the directory name, so steps past
the 8-digit zero-pad (or older checkpoints written with a different
width) order correctly.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

import jax

PrunePolicy = Union[int, Tuple, Callable[[List[int]], Any]]

_STEP_RE = re.compile(r"^step_(\d+)$")


def _flatten_with_names(tree) -> Dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[name] = leaf
    return out


def _list_steps(ckpt_dir: str) -> List[Tuple[int, str]]:
    """On-disk checkpoints as (step int, dirname), ascending by step."""
    out = []
    for d in os.listdir(ckpt_dir):
        m = _STEP_RE.match(d)
        if m and os.path.isdir(os.path.join(ckpt_dir, d)):
            out.append((int(m.group(1)), d))
    out.sort()
    return out


def resolve_prune_policy(policy: PrunePolicy) -> Callable[[List[int]], set]:
    """Normalize a prune-policy spec to ``steps -> set(steps to keep)``.

    See the module docstring for the accepted forms. Raises ValueError
    (named) for malformed specs so a bad config fails at save time, not
    by silently keeping everything.
    """
    if callable(policy):
        return lambda steps: set(policy(steps))
    if isinstance(policy, int) and not isinstance(policy, bool):
        if policy <= 0:
            raise ValueError(f"prune_policy keep_last={policy} must be "
                             "positive")
        return lambda steps: set(steps[-policy:])
    if isinstance(policy, tuple) and policy:
        if policy[0] == "keep_last" and len(policy) == 2:
            return resolve_prune_policy(policy[1])
        if policy[0] == "keep_every_n" and len(policy) == 3:
            _, n, k = policy
            if not (isinstance(n, int) and n > 0):
                raise ValueError(f"keep_every_n period must be a "
                                 f"positive int, got {n!r}")
            keep_last = resolve_prune_policy(k)
            return lambda steps: ({s for s in steps if s % n == 0}
                                  | keep_last(steps))
    raise ValueError(
        f"unknown prune_policy {policy!r}; want an int, "
        "('keep_last', k), ('keep_every_n', n, k), or a callable")


def save_checkpoint(ckpt_dir: str, step: int, tree,
                    metadata: Optional[dict] = None,
                    keep_last: Optional[int] = None,
                    prune_policy: Optional[PrunePolicy] = None,
                    extra_arrays: Optional[Dict[str, Any]] = None) -> str:
    """Atomically write checkpoint `step`; prune old ones by policy.

    ``keep_last`` is the legacy spelling of ``prune_policy=k`` and is
    kept for existing callers; passing both is an error. With neither,
    the default is keep-last-3. ``extra_arrays`` are host arrays stored
    beside the tree's under their own names (read back with
    `load_arrays`), which no name of the tree's may take.
    """
    if keep_last is not None and prune_policy is not None:
        raise ValueError("save_checkpoint: pass either keep_last "
                         "(legacy) or prune_policy, not both")
    if prune_policy is None:
        prune_policy = 3 if keep_last is None else keep_last
    keep_fn = resolve_prune_policy(prune_policy)  # fail before writing
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + f".tmp.{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    named = _flatten_with_names(tree)
    arrays = {k: np.asarray(jax.device_get(v)) for k, v in named.items()}
    for k, v in (extra_arrays or {}).items():
        if k in arrays:
            raise ValueError(f"save_checkpoint: extra array {k!r} "
                             "takes the name of a tree leaf")
        arrays[k] = np.asarray(v)
    np.savez_compressed(os.path.join(tmp, "arrays.npz"), **arrays)
    manifest = {"step": step, "time": time.time(),
                "n_arrays": len(arrays),
                "metadata": metadata or {}}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic publish
    _prune(ckpt_dir, keep_fn, just_written=step)
    return final


def _prune(ckpt_dir: str, keep_fn: Callable[[List[int]], set],
           just_written: Optional[int] = None):
    entries = _list_steps(ckpt_dir)
    if not entries:
        return
    steps = [s for s, _ in entries]
    keep = set(keep_fn(steps))
    # The checkpoint this save just wrote always survives — even when a
    # reused directory holds numerically higher steps from an older run.
    keep.add(steps[-1] if just_written is None else just_written)
    for s, d in entries:
        if s not in keep:
            shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    """Highest-*step* complete checkpoint (numeric ordering)."""
    if not os.path.isdir(ckpt_dir):
        return None
    complete = [(s, d) for s, d in _list_steps(ckpt_dir)
                if os.path.exists(os.path.join(ckpt_dir, d,
                                               "manifest.json"))]
    return os.path.join(ckpt_dir, complete[-1][1]) if complete else None


def load_manifest(path: str) -> dict:
    """The checkpoint's manifest (step, time, metadata)."""
    with open(os.path.join(path, "manifest.json")) as f:
        return json.load(f)


def load_arrays(path: str, names) -> Dict[str, np.ndarray]:
    """The named arrays of checkpoint ``path`` (tree leaves or extra
    arrays), read into memory."""
    with np.load(os.path.join(path, "arrays.npz")) as data:
        return {k: data[k] for k in names}


def restore_checkpoint(path: str, template,
                       manifest: Optional[dict] = None) -> Tuple[Any, dict]:
    """Restore into the structure of `template` (arrays or structs).

    Callers that already loaded the manifest (e.g. to build the template
    from its metadata) can pass it to avoid a second read.
    """
    if manifest is None:
        manifest = load_manifest(path)
    data = np.load(os.path.join(path, "arrays.npz"))
    named = _flatten_with_names(template)
    flat, tdef = jax.tree_util.tree_flatten(template)
    restored = []
    names = list(named.keys())
    assert len(names) == len(flat)
    for name, leaf in zip(names, flat):
        arr = data[name]
        want = tuple(leaf.shape)
        if tuple(arr.shape) != want:
            raise ValueError(f"{name}: checkpoint {arr.shape} != {want}")
        restored.append(arr)
    return jax.tree_util.tree_unflatten(tdef, restored), manifest
