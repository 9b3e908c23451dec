"""Sharding rules: parameter/batch/cache PartitionSpecs per architecture.

Strategy (DESIGN.md §5):

* **Parameters** — 2D-sharded: the tensor-parallel dim (heads / d_ff /
  experts / vocab) over ``model``, the other large dim over ``data``
  (FSDP/ZeRO-3 posture: all-gathered at use, grads reduce-scattered by
  GSPMD).  Replicated over ``pod`` (pure DP across pods → hierarchical
  all-reduce on the slow axis).
* **Optimizer state** — same specs as its parameter.
* **Batch** — global batch over ("pod","data"); sequence unsharded.
* **KV cache / SSM state** — batch over data axes, heads/channels over
  ``model``.

Rules are path-pattern based over the param pytree so every architecture
family (dense / MoE / SSM / hybrid / enc-dec) is covered by one table.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig


# stacked containers whose leaves carry a leading layer/group dim
_STACKED = ("groups", "encoder", "decoder")


def _path_str(path) -> str:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def _param_spec(path: str, shape: tuple[int, ...], mesh: Mesh,
                cfg: ArchConfig) -> P:
    axes = set(mesh.axis_names)
    dp = "data" if "data" in axes else None
    tp = "model" if "model" in axes else None
    nd = len(shape)
    stacked = any(path.startswith(s + "/") or f"/{s}/" in path for s in _STACKED)
    lead = (None,) if stacked else ()
    body = shape[1:] if stacked else shape
    bn = len(body)

    def spec(*xs):
        return P(*(lead + tuple(xs)))

    # ---- embeddings ------------------------------------------------------
    if path.endswith("embed/tok"):
        return spec(tp, dp)
    if path.endswith("embed/pos"):
        return spec(None, tp)
    if path.endswith("lm_head/w"):
        return spec(dp, tp)

    # ---- norms / scalars ---------------------------------------------------
    if bn <= 1:
        return spec(*([None] * bn))

    # ---- MoE expert banks (leading E dim) ----------------------------------
    if "/mlp/" in path and bn == 3:
        E = body[0]
        tp_sz = mesh.shape.get("model", 1)
        if E >= tp_sz:
            # expert parallelism over `model`
            if path.endswith("w_out"):
                return spec(tp, None, dp)
            return spec(tp, dp, None)
        # few experts: shard the ffn dim instead
        if path.endswith("w_out"):
            return spec(None, tp, dp)
        return spec(None, dp, tp)
    if path.endswith("mlp/router/w"):
        return spec(dp, None)

    # ---- attention ---------------------------------------------------------
    if any(path.endswith(s) for s in ("wq/w", "wk/w", "wv/w")):
        return spec(dp, tp)
    if path.endswith("wo/w"):
        return spec(tp, dp)
    if any(path.endswith(s) for s in ("wq/b", "wk/b", "wv/b")):
        return spec(tp)

    # ---- dense FFN ---------------------------------------------------------
    if any(path.endswith(s) for s in ("w_in/w", "w_gate/w")):
        return spec(dp, tp)
    if path.endswith("w_out/w"):
        return spec(tp, dp)
    if any(path.endswith(s) for s in ("w_in/b", "w_gate/b")):
        return spec(tp)

    # ---- SSM ----------------------------------------------------------------
    if path.endswith("in_proj/w"):
        return spec(dp, tp)
    if path.endswith("out_proj/w"):
        return spec(tp, dp)
    if path.endswith("ssm/conv"):
        return spec(None, tp)

    # ---- RG-LRU -------------------------------------------------------------
    if any(path.endswith(s) for s in ("in_x/w", "in_y/w", "w_a/w", "w_i/w")):
        return spec(dp, tp)
    if path.endswith("rec/out/w"):
        return spec(tp, dp)
    if path.endswith("rec/conv"):
        return spec(None, tp)

    # default: shard the biggest dim over model when divisible, else replicate
    body_specs: list[Any] = [None] * bn
    big = int(np.argmax(body))
    if tp and body[big] % mesh.shape["model"] == 0:
        body_specs[big] = tp
    return spec(*body_specs)


class ShardingSpecError(ValueError):
    """A PartitionSpec names a mesh axis that does not exist or does not
    divide the dim it shards (raised by :func:`sanitize_spec` in strict
    mode instead of silently truncating the spec)."""


def sanitize_spec(spec: P, shape: tuple[int, ...], mesh: Mesh, *,
                  strict: bool = True, path: str = "") -> P:
    """Validate ``spec`` against ``shape`` on ``mesh``.

    Strict (the default): raise :class:`ShardingSpecError` when a named
    axis is missing from the mesh or does not divide its dim evenly (pjit
    input shardings require equal shards) — a spec that silently degrades
    to replicated is a perf cliff, not a preference.

    ``strict=False`` restores the historical best-effort behavior — drop
    the offending axes and keep the rest — which is what the heuristic
    rule tables here want (e.g. long_500k's global_batch=1 legitimately
    turns its batch sharding off).  ``path`` labels errors with the pytree
    location.
    """
    if len(spec) > len(shape):
        raise ShardingSpecError(
            f"{path or 'spec'}: PartitionSpec{tuple(spec)} has "
            f"{len(spec)} entries for shape {tuple(shape)} of rank "
            f"{len(shape)}")
    out = []
    where = f" at {path!r}" if path else ""
    for d, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        size = shape[d]
        for a in axes:
            n = mesh.shape.get(a)
            if n is None:
                if strict:
                    raise ShardingSpecError(
                        f"spec{where} names mesh axis {a!r} on dim {d}, "
                        f"but the mesh only has axes "
                        f"{tuple(mesh.axis_names)}")
                continue
            if size % n == 0:
                keep.append(a)
                size //= n
            elif strict:
                raise ShardingSpecError(
                    f"spec{where} shards dim {d} (size {shape[d]}) over "
                    f"mesh axis {a!r} (size {n}), which does not divide "
                    f"it evenly; pass strict=False to drop the axis "
                    f"instead")
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*out)


def param_specs(params_or_shapes: Any, mesh: Mesh, cfg: ArchConfig, *,
                strict: bool = False) -> Any:
    """PartitionSpec pytree mirroring the param pytree.

    The rule table is a placement *preference*, so by default dims the
    mesh cannot divide fall back to replicated.  ``strict=True`` turns
    every such fallback into a :class:`ShardingSpecError` naming the
    parameter — use it in tests/CI to prove a config shards cleanly on a
    given mesh.
    """

    def rule(path, leaf):
        p = _path_str(path)
        spec = _param_spec(p, tuple(leaf.shape), mesh, cfg)
        return sanitize_spec(spec, tuple(leaf.shape), mesh,
                             strict=strict, path=p)

    return jax.tree_util.tree_map_with_path(rule, params_or_shapes)


def param_shardings(params_or_shapes: Any, mesh: Mesh, cfg: ArchConfig) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_or_shapes, mesh, cfg))


def as_shardings(specs: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree.

    Version compat for ``jax.jit`` in/out_shardings: newer JAX resolves bare
    PartitionSpecs against the ambient mesh, older releases require
    ``Sharding`` objects.  ``None`` leaves (infer/replicate) pass through.
    """
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(batch: Any, mesh: Mesh) -> Any:
    """Batch dim over (pod, data); everything else replicated."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def rule(leaf):
        nd = len(leaf.shape)
        if not nd:
            return P()
        return sanitize_spec(P(dp, *([None] * (nd - 1))), tuple(leaf.shape),
                             mesh, strict=False)

    return jax.tree.map(rule, batch)


def cache_specs(cache: Any, mesh: Mesh, cfg: ArchConfig) -> Any:
    """Decode-cache specs: batch over data axes, head/channel dim over model.

    Cache leaf layouts (leading group dim G when stacked):
      kv        (G, B, C, Hkv, hd)
      rglru h   (G, B, D)          rglru conv (G, B, 3, D)
      ssm state (G, B, H, P, N)    ssm conv   (G, B, cw-1, ch)
      enc_out   (B, F, D)
      pos       ()
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def rule(path, leaf):
        p = _path_str(path)
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        stacked = p.startswith(("groups", "layers")) or "/groups/" in p
        lead = (None,) if stacked else ()
        body = leaf.shape[1:] if stacked else leaf.shape
        bn = len(body)
        if p.endswith("enc_out"):
            spec = P(dp, None, None)
        elif bn == 4 and p.split("/")[-1] in ("k", "v"):
            # (B, C, Hkv, hd): shard heads over model when divisible,
            # otherwise shard the cache sequence dim (partial-softmax
            # reductions over C are GSPMD-expressible)
            tp_sz = mesh.shape.get("model", 1)
            if body[2] % tp_sz == 0:
                spec = P(*(lead + (dp, None, "model", None)))
            else:
                spec = P(*(lead + (dp, "model", None, None)))
        elif bn == 4:                                        # ssm state (B,H,P,N)
            spec = P(*(lead + (dp, "model", None, None)))
        elif bn == 3:                                        # conv buffers
            spec = P(*(lead + (dp, None, "model")))
        elif bn == 2:                                        # rglru h (B,D)
            spec = P(*(lead + (dp, "model")))
        else:
            spec = P(*(lead + (dp,) + (None,) * (bn - 1)))
        return sanitize_spec(spec, tuple(leaf.shape), mesh,
                             strict=False, path=p)

    return jax.tree_util.tree_map_with_path(rule, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------
# In-model sharding hints
# --------------------------------------------------------------------------

BATCH = ("pod", "data")  # logical batch axes


def shard_hint(x, *axes):
    """``with_sharding_constraint`` that adapts to whatever mesh is current.

    ``axes`` entries are mesh-axis names, tuples of names, or None; names
    missing from the current mesh and dims the axes don't divide are
    dropped.  Outside any mesh this is the identity, so model code can
    sprinkle hints without caring about the execution context.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return x
    if mesh is None or not mesh.axis_names:
        return x
    names = set(mesh.axis_names)
    spec = []
    for d, a in enumerate(axes):
        cand = a if isinstance(a, tuple) else ((a,) if a else ())
        keep, size = [], x.shape[d]
        for nm in cand:
            if nm in names and size % mesh.shape[nm] == 0:
                keep.append(nm)
                size //= mesh.shape[nm]
        spec.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return jax.lax.with_sharding_constraint(x, P(*spec))
