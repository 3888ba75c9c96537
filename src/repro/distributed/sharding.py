"""Sharding rules: logical parameter/activation layouts -> PartitionSpecs.

Mesh axes:
* ``pod``   — pure data parallelism across ICI-disconnected pods (DCN).
* ``data``  — intra-pod data parallelism (and ZeRO-1 optimizer sharding).
* ``model`` — tensor parallelism: attention heads, FFN hidden, MoE experts,
              vocab, SSM inner channels.

Every rule is divisibility-checked against the mesh: a dimension that does
not divide (e.g. smollm's 9 heads on a 16-way model axis) falls back to
replication for that axis — the framework logs the decision instead of
failing, which is what lets one sharding config serve 10 heterogeneous
architectures.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

MESH_AXES = ("pod", "data", "model")


def _axis_size(mesh: Mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.shape else 1


def _fit(dim: int, mesh: Mesh, axis: str) -> str | None:
    """Return the axis if dim divides its size, else None (replicate)."""
    if dim % _axis_size(mesh, axis) == 0:
        return axis
    log.info("sharding fallback: dim %d !%% %s=%d -> replicated",
             dim, axis, _axis_size(mesh, axis))
    return None


# rules: param leaf name -> function(shape, mesh) -> PartitionSpec
def _spec_for(name: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    m = "model"
    if name in ("embed",):                       # (V, d)
        return P(_fit(shape[0], mesh, m), None)
    if name in ("head",):                        # (d, V)
        return P(None, _fit(shape[1], mesh, m))
    if name == "wq":                             # (d, H, hd)
        return P(None, _fit(shape[1], mesh, m), None)
    if name in ("wk", "wv"):                     # (d, KV, hd)
        return P(None, _fit(shape[1], mesh, m), None)
    if name == "wo":                             # (H, hd, d)
        return P(_fit(shape[0], mesh, m), None, None)
    if name in ("wg", "wu"):
        if len(shape) == 3:                      # MoE experts (E, d, f)
            return P(_fit(shape[0], mesh, m), None, None)
        return P(None, _fit(shape[1], mesh, m))  # dense (d, f)
    if name == "wd":
        if len(shape) == 3:                      # (E, f, d)
            return P(_fit(shape[0], mesh, m), None, None)
        return P(_fit(shape[0], mesh, m), None)  # (f, d)
    if name == "router":                         # (d, E)
        return P(None, _fit(shape[1], mesh, m))
    if name in ("wx",):                          # ssd (d, 2*din)
        return P(None, _fit(shape[1], mesh, m))
    if name in ("wdt",):                         # (d, H)
        return P(None, _fit(shape[1], mesh, m))
    if name in ("dt_bias", "a_log"):             # (H,)
        return P(_fit(shape[0], mesh, m))
    if name in ("wbc",):                         # (d, 2N) — small, replicate
        return P(None, None)
    if name in ("w_in", "w_gate", "w_r", "w_i"):  # lru (d|dr, dr)
        return P(None, _fit(shape[1], mesh, m))
    if name in ("b_r", "b_i", "lam"):            # (dr,)
        return P(_fit(shape[0], mesh, m))
    if name in ("w_out", "wo2"):                 # (dr|din, d)
        return P(_fit(shape[0], mesh, m), None)
    # norms, biases, everything else: replicate
    return P(*([None] * len(shape)))


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
        if hasattr(entry, "name"):
            return str(entry.name)
    return ""


def param_shardings(params_shape: Any, mesh: Mesh) -> Any:
    """Map a pytree of ShapeDtypeStructs (or arrays) to NamedShardings.
    Stacked layer dims (from scan-over-layers) are detected by rank: specs
    are right-aligned to the trailing dims the rule describes."""

    def one(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        # segment params carry a leading layer-stack dim; rules address the
        # block-local shape.  Detect by trying the rule on the trailing dims.
        spec = _spec_for(name, shape, mesh)
        if len(spec) < len(shape):
            spec = P(*([None] * (len(shape) - len(spec)) + list(spec)))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def _rule_rank(name: str) -> int | None:
    ranks = {
        "embed": 2, "head": 2, "wq": 3, "wk": 3, "wv": 3, "wo": 3,
        "router": 2, "wx": 2, "wdt": 2, "dt_bias": 1, "a_log": 1, "wbc": 2,
        "w_in": 2, "w_gate": 2, "w_r": 2, "w_i": 2, "b_r": 1, "b_i": 1,
        "lam": 1, "w_out": 2,
    }
    return ranks.get(name)


def param_shardings_stacked(params_shape: Any, mesh: Mesh,
                            fsdp: bool = False,
                            fsdp_min_elems: int = 1 << 20) -> Any:
    """Like param_shardings but resolves the rule on the trailing
    ``rule_rank`` dims (robust for stacked MoE/dense ambiguity).

    ``fsdp=True`` additionally shards the first still-replicated divisible
    dim of every large tensor over "data" (FSDP / ZeRO-3 weight sharding via
    GSPMD — XLA inserts the per-layer all-gathers).  Required to fit
    235B-class MoE params + moments on 16 GB/chip hardware.
    """
    d = _axis_size(mesh, "data")

    def one(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        rr = _rule_rank(name)
        if name in ("wg", "wu", "wd"):
            # disambiguate dense (2) vs moe (3) by the segment kind in path
            kinds = [str(getattr(e, "key", "")) for e in path]
            rr = 3 if any("moe" in k for k in kinds) else 2
        if rr is None or rr > len(shape):
            rr = len(shape)
        spec = list(_spec_for(name, shape[len(shape) - rr:], mesh))
        spec = [None] * (len(shape) - rr) + spec
        if fsdp and int(np.prod(shape)) >= fsdp_min_elems and d > 1:
            for i in range(len(shape) - rr, len(shape)):
                if spec[i] is None and shape[i] % d == 0 and shape[i] >= d:
                    spec[i] = "data"
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_axes_for(global_batch: int, mesh: Mesh) -> Tuple[str, ...]:
    """Best batch sharding: ("pod","data") -> ("data",) -> () by
    divisibility."""
    pd = _axis_size(mesh, "pod") * _axis_size(mesh, "data")
    if global_batch % pd == 0:
        return tuple(a for a in ("pod", "data") if a in mesh.shape)
    d = _axis_size(mesh, "data")
    if global_batch % d == 0 and "data" in mesh.shape:
        return ("data",)
    return ()


def batch_shardings(batch_shape: Any, mesh: Mesh, global_batch: int) -> Any:
    axes = batch_axes_for(global_batch, mesh)
    spec_axes = axes if axes else None

    def one(leaf):
        spec = [spec_axes] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch_shape)


def opt_shardings(param_sharding: Any, params_shape: Any, mesh: Mesh,
                  zero1: bool = False) -> Any:
    """Optimizer-moment shardings.  With ``zero1``, moments additionally
    shard their first still-replicated, divisible dim over "data"
    (ZeRO-1-style optimizer-state partitioning)."""
    if not zero1:
        return param_sharding
    d = _axis_size(mesh, "data")

    def one(sh, leaf):
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        if "data" in spec:      # already data-sharded (e.g. FSDP weights)
            return NamedSharding(mesh, P(*spec))
        for i, (s, dim) in enumerate(zip(spec, leaf.shape)):
            if s is None and dim % d == 0 and dim >= d:
                spec[i] = "data"
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, param_sharding, params_shape)


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with explicit ``Auto`` axis types."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


def mesh_context(mesh):
    """The sharding context of ``mesh`` (``jax.sharding.set_mesh``)."""
    return jax.sharding.set_mesh(mesh)


def constrain(x, *axes):
    """Activation sharding constraint by logical axes; no-op without a mesh
    context.  ``axes`` entries are mesh axis names, tuples, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)

    def ok(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            sub = tuple(x_ for x_ in a if x_ in names)
            return sub if sub else None
        return a if a in names else None

    spec = P(*[ok(a) for a in axes])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # pragma: no cover - defensive
        return x
