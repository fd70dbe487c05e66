"""Logical-axis sharding.

Models annotate tensors with *logical* axis names; a rules context maps those
to mesh axes (flaxformer-style).  Outside a rules context every annotation is a
no-op, so the same model code runs on a single CPU device, under pjit with a
(data, model) mesh, or inside a partial-auto shard_map.

Manual-collective code (e.g. the compressed gradient all-reduce) enters
shard_map through :func:`manual_shard_map`, which fixes its replication
check off.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule sets.  Values may name mesh axes that do not exist in the active mesh;
# missing axes are dropped at resolution time, so one rule set serves both the
# single-pod (data, model) and multi-pod (pod, data, model) meshes.
# ---------------------------------------------------------------------------

TRAIN_RULES: Dict[str, AxisVal] = {
    'batch': ('pod', 'data'),
    'seq': 'model',          # Megatron-style sequence parallelism on residuals
    'embed': None,
    'heads': 'model',
    'kv_heads': 'model',
    'head_dim': None,
    'qkv': 'model',          # fused q/k/v output dim
    'ffn': 'model',
    'vocab': 'model',
    'expert': 'model',       # expert parallelism
    'layers': None,
    'pages': None,
    'state': None,
}

# Decode/prefill: region-paged KV (per-request page regions) makes the page
# gather a batch-aligned take_along_axis, so serving shards under pure pjit —
# batch over (pod, data), tensor-parallel dims over model.
SERVE_RULES: Dict[str, AxisVal] = {
    'batch': ('pod', 'data'),
    'seq': None,
    'embed': None,
    'heads': 'model',
    'kv_heads': 'model',
    'head_dim': None,
    'qkv': 'model',
    'ffn': 'model',
    'vocab': 'model',
    'expert': 'model',
    'layers': None,
    'pages': None,
    'kv_seq': None,
    'state': None,
}

# long_500k (global_batch=1): nothing to shard on batch — the KV sequence dim
# itself is sharded over (pod, data) (sequence-parallel decode; XLA inserts the
# partial-softmax collectives).
LONG_SERVE_RULES: Dict[str, AxisVal] = dict(
    SERVE_RULES, batch=None, kv_seq=('pod', 'data'))

# ---------------------------------------------------------------------------
# §Perf hillclimb variants (see EXPERIMENTS.md §Perf for the iteration log)
# ---------------------------------------------------------------------------

# Decode H1 — contract-over-Dh: shard q AND the KV pool on head_dim (heads
# replicated).  The attention contractions then reduce over a dim that is
# sharded on BOTH operands, so XLA emits partial-score psums
# (≈ B·H·S f32 per device) instead of all-gathering the full KV
# (≈ B·S·Hkv·Dh bf16 — ~64× more wire for Dh=128/16-way).
SERVE_DH_CONTRACT_RULES: Dict[str, AxisVal] = dict(
    SERVE_RULES, heads=None, kv_heads=None, head_dim='model', qkv=None)

# Decode H2 — sequence-parallel KV: shard the page/region dim of the pool
# over the model axis; each shard attends over its local pages and XLA
# reduces the partial softmax stats + outputs (tiny collectives).
SERVE_SEQ_RULES: Dict[str, AxisVal] = dict(
    SERVE_RULES, pages='model', kv_seq='model')

# Decode H3 — data-parallel attention: the KV pool replicates over the model
# axis (batch stays on data); attention is collective-free and the model
# axis serves only the projections/MLP/vocab.  Costs HBM capacity
# (replicated KV) — viable when B/|data| × S × KV-bytes fits.
SERVE_KV_DP_RULES: Dict[str, AxisVal] = dict(
    SERVE_RULES, heads=None, kv_heads=None, head_dim=None)

# Train H1 — no sequence parallelism on the residual stream: trades the
# per-layer-boundary all-gather/reduce-scatter pairs for replicated
# activations (more HBM, less wire).
TRAIN_NO_SP_RULES: Dict[str, AxisVal] = dict(TRAIN_RULES, seq=None)

RULE_VARIANTS = {
    'default': None,                      # resolved per shape kind
    'serve_dh': SERVE_DH_CONTRACT_RULES,
    'serve_seq': SERVE_SEQ_RULES,
    'serve_kv_dp': SERVE_KV_DP_RULES,
    'train_no_sp': TRAIN_NO_SP_RULES,
}

_tls = threading.local()


def _current() -> Optional[Tuple[Mesh, Dict[str, AxisVal]]]:
    return getattr(_tls, 'ctx', None)


@contextlib.contextmanager
def axis_rules(mesh: Optional[Mesh], rules: Dict[str, AxisVal]):
    prev = _current()
    _tls.ctx = (mesh, rules) if mesh is not None else None
    try:
        yield
    finally:
        _tls.ctx = prev


def logical_to_spec(axes: Sequence[Optional[str]],
                    rules: Dict[str, AxisVal],
                    mesh: Optional[Mesh] = None) -> P:
    """Map logical axis names to a PartitionSpec, dropping absent mesh axes.

    A rule whose mapped axes are *all* absent from the mesh resolves to
    ``None`` (replicated) — never a stale name tuple.  ``mesh=None`` has no
    axes at all, so every mapping degrades to replicated; the old behavior
    (pass the rule tuple through unfiltered) produced specs naming axes no
    mesh provides, which ``NamedSharding`` rejects.
    """
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    used: set = set()
    parts = []
    for ax in axes:
        val = rules.get(ax) if ax is not None else None
        if val is None:
            parts.append(None)
            continue
        val_t = (val,) if isinstance(val, str) else tuple(val)
        val_t = tuple(v for v in val_t if v in mesh_axes)
        val_t = tuple(v for v in val_t if v not in used)
        used.update(val_t)
        if not val_t:
            parts.append(None)
        elif len(val_t) == 1:
            parts.append(val_t[0])
        else:
            parts.append(val_t)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def manual_shard_map(fn, mesh: Mesh, in_specs, out_specs, *,
                     check_replication: bool = False):
    """``jax.shard_map`` for manual-collective code.

    ``check_replication=False`` (``check_vma``) is the default our
    collectives rely on: psum of int8 payloads is replication-breaking by
    design.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


def constrain(x, axes: Sequence[Optional[str]]):
    """with_sharding_constraint by logical axes; no-op outside a rules context."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shaped_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                rules: Dict[str, AxisVal], mesh: Mesh) -> P:
    """Shape-aware resolution for jit *arguments* (which must divide evenly,
    unlike intermediates).

    Mesh axes whose size does not divide the mapped dimension are dropped
    from that dimension and re-placed on the last unsharded, divisible
    dimension instead (e.g. 8 KV heads can't shard over model=16 → the
    model axis moves to head_dim=128).  Deterministic, so lowering and
    restore agree.
    """
    sizes = dict(mesh.shape)
    used: set = set()
    groups: list = []
    freed: list = []
    for dim, ax in zip(shape, axes):
        val = rules.get(ax) if ax is not None else None
        if val is None:
            groups.append([])
            continue
        val_t = (val,) if isinstance(val, str) else tuple(val)
        val_t = [v for v in val_t if v in sizes and v not in used]
        # drop trailing axes until the product divides the dim
        while val_t:
            prod = 1
            for v in val_t:
                prod *= sizes[v]
            if dim % prod == 0:
                break
            freed.append(val_t.pop())
        used.update(val_t)
        groups.append(list(val_t))
    # re-place freed axes on the last divisible unsharded dims
    for v in freed:
        for i in range(len(groups) - 1, -1, -1):
            if not groups[i] and shape[i] % sizes[v] == 0 and shape[i] > 1:
                groups[i].append(v)
                used.add(v)
                break
    parts = [None if not g else (g[0] if len(g) == 1 else tuple(g))
             for g in groups]
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def spec_for(axes: Sequence[Optional[str]],
             rules: Dict[str, AxisVal],
             mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, rules, mesh))


def tree_spec(logical_tree, rules: Dict[str, AxisVal], mesh: Mesh):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: spec_for(axes, rules, mesh),
        logical_tree,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t),
    )


def _is_axes_leaf(t):
    return isinstance(t, tuple) and all(
        a is None or isinstance(a, str) for a in t)


def tree_spec_shaped(logical_tree, shapes_tree, rules: Dict[str, AxisVal],
                     mesh: Mesh):
    """Shape-aware tree_spec for jit argument shardings."""
    flat_axes, tdef = jax.tree.flatten(logical_tree, is_leaf=_is_axes_leaf)
    flat_shapes = tdef.flatten_up_to(shapes_tree)
    out = [NamedSharding(mesh, shaped_spec(tuple(s.shape), a, rules, mesh))
           for a, s in zip(flat_axes, flat_shapes)]
    return jax.tree.unflatten(tdef, out)
