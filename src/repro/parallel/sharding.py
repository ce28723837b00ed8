"""Logical-axis → mesh-axis sharding rules (DP / TP / EP / SP / FSDP).

Parameters declare *logical* axis names in their PDefs; this module turns
them into PartitionSpecs for a concrete mesh.  Assignment is greedy per
parameter: each logical axis tries its candidate mesh axes in order, skipping
axes already used by an earlier dim of the same tensor and axes that do not
divide the dim size.  That one mechanism expresses:

* TP   — "heads"/"ffn"/"vocab" → model
* EP   — "experts" → model (expert FFN dims then fall through to data/pod)
* FSDP — with ``fsdp=True``, "embed" (and overflow "ffn") shard over
         data (and pod on the multi-pod mesh), ZeRO-sharding the master
         params + Adam state of the 100B+ archs across the whole fleet
* DP   — "batch" on activations → (pod, data)
* SP   — "kv_seq" on long-context caches/activations → model

Anything that does not fit stays replicated — the dry-run then proves which
combination compiles and fits HBM for every (arch × shape) cell.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.params import PDef, is_pdef


def _candidates(fsdp: bool) -> Dict[Optional[str], Tuple[str, ...]]:
    return {
        None: (),
        "layers": (),
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        # "data" fallback: when `model` is taken by the experts dim (EP), the
        # expert FFN dim shards over data — required to fit MoE weights at
        # serving time (no FSDP there) and harmless for dense archs (model
        # wins first).  Under FSDP, pod is the final overflow.
        "ffn": ("model", "data", "pod") if fsdp else ("model", "data"),
        "experts": ("model",),
        "embed": ("data", "pod") if fsdp else (),
        "state": (),
        "kv_seq": ("model",),
        "batch": ("pod", "data"),   # params never use this; activations do
        "hidden": (),
        "cell_in": (),
        "cell_out": (),
    }


def spec_for(defn: PDef, mesh_axes: Dict[str, int], fsdp: bool) -> P:
    cands = _candidates(fsdp)
    used: set = set()
    out = []
    for dim, name in zip(defn.shape, defn.axes):
        if name == "batch":
            # batch shards over the full DP product: ("pod","data")
            axes = []
            rem = dim
            for ax in cands["batch"]:
                if ax in mesh_axes and ax not in used and rem % mesh_axes[ax] == 0:
                    axes.append(ax)
                    used.add(ax)
                    rem //= mesh_axes[ax]
            out.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
            continue
        assigned = None
        for ax in cands.get(name, ()):  # unknown logical names -> replicated
            if ax in mesh_axes and ax not in used and dim % mesh_axes[ax] == 0:
                assigned = ax
                used.add(ax)
                break
        out.append(assigned)
    return P(*out)


def param_specs(defs, mesh: Mesh, fsdp: bool = False):
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree.map(lambda d: spec_for(d, axes, fsdp), defs, is_leaf=is_pdef)


def param_shardings(defs, mesh: Mesh, fsdp: bool = False):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(defs, mesh, fsdp))


# ---------------------------------------------------------------- activations
def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch dim: ('pod','data') or ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_dim_spec(dim: int, mesh: Mesh):
    """DP axes that actually divide this batch size (batch=1 ⇒ replicate)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = []
    rem = dim
    for a in batch_axes(mesh):
        if rem % sizes[a] == 0:
            axes.append(a)
            rem //= sizes[a]
    if not axes:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def act_spec(mesh: Mesh, *axes: Optional[str]) -> P:
    """Build an activation PartitionSpec: 'batch'→(pod,data), 'model'→model."""
    out = []
    for a in axes:
        if a == "batch":
            ba = batch_axes(mesh)
            out.append(ba if len(ba) > 1 else (ba[0] if ba else None))
        else:
            out.append(a if a in mesh.axis_names else None)
    return P(*out)


def constrain(x, mesh: Mesh, *axes: Optional[str]):
    """with_sharding_constraint via logical activation axes (size-aware)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for dim, a in zip(x.shape, axes):
        if a == "batch":
            out.append(batch_dim_spec(dim, mesh))
        elif a in sizes and dim % sizes[a] == 0:
            out.append(a)
        else:
            out.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*out)))


def shard_batch(x, mesh: Mesh):
    """Place a host batch on the mesh, dim 0 sharded over the DP axes.

    Used by the serving paths (e.g. the integer LUT engine's request
    batches) so inputs land already distributed instead of replicated and
    re-sharded by the first ``with_sharding_constraint`` inside the jit.
    """
    spec = P(batch_dim_spec(x.shape[0], mesh), *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def pad_batch(x, n_rows: int):
    """Zero-pad dim 0 of a host batch up to ``n_rows``.

    The serving scheduler coalesces requests into power-of-two buckets so the
    jit cache stays small and every bucket size divides the DP axes of any
    power-of-two mesh; this is the padding step (zero codes are always valid
    inputs — the integer engines accept any in-range code and padded rows are
    simply dropped at scatter time).
    """
    if x.shape[0] > n_rows:
        raise ValueError(f"batch of {x.shape[0]} rows does not fit a "
                         f"{n_rows}-row bucket")
    if x.shape[0] == n_rows:
        return x
    pad = [(0, n_rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return np.pad(np.asarray(x), pad)


def heads_shardable(n_heads: int, mesh: Mesh) -> bool:
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return "model" in axes and n_heads % axes["model"] == 0
