"""Sharding rules: logical parameter layout for the production mesh.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod. The layout is FSDP×TP (MaxText-style):

- weights:   d_model dim sharded over ``data`` (FSDP — ZeRO-3 gathers are
  GSPMD-inserted all-gathers), head/ffn/vocab dim over ``model`` (TP);
- MoE expert stacks: expert dim over ``model`` (EP);
- batch dims of activations over ``("pod", "data")``;
- the ``pod`` axis only carries data parallelism — cross-pod traffic is the
  gradient all-reduce, which is what the compression path targets.

An axis is applied to a dim only when the dim is divisible by (and at least
as large as) the axis size, else that dim stays replicated — the documented
fallbacks (e.g. kv-head counts below 16). Vocab dims are padded to 128 at
the embedding layer so they always divide.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def abstract_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """An ``AbstractMesh`` of this shape: sharding rules only need the mesh
    shape, so no devices are needed."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


# Ordered (path-regex, spec-template) rules. Templates name mesh axes per
# dim; "_" = replicated. Matched against "/".join(path keys).
_RULES: list[tuple[str, tuple]] = [
    # embeddings
    (r"embedding$",              ("model", "data")),
    (r"lm_head$",                ("data", "model")),
    (r"(enc_pos|dec_pos)$",      ("_", "data")),
    # attention projections (stacked: leading layer dim)
    (r"attn/wq$",                ("_", "data", "model")),
    (r"attn/wk$",                ("_", "data", "model")),
    (r"attn/wv$",                ("_", "data", "model")),
    (r"attn/wo$",                ("_", "model", "data")),
    # dense mlp
    (r"mlp/w_(gate|up)$",        ("_", "data", "model")),
    (r"mlp/w_down$",             ("_", "model", "data")),
    # shared-expert mlp
    (r"shared/w_(gate|up)$",     ("_", "data", "model")),
    (r"shared/w_down$",          ("_", "model", "data")),
    # MoE expert stacks: (L, E, D, F) — EP over model
    (r"experts/w_(gate|up)$",    ("_", "model", "data", "_")),
    (r"experts/w_down$",         ("_", "model", "_", "data")),
    (r"router$",                 ("_", "data", "_")),
    # ssm
    (r"in_proj$",                ("_", "data", "model")),
    (r"out_proj$",               ("_", "model", "data")),
    (r"conv_w$",                 ("_", "_", "model")),
    # griffin recurrent blocks
    (r"w_[xy]$",                 ("_", "data", "model")),
    (r"w_[ai]$",                 ("_", "data", "model")),
    (r"w_out$",                  ("_", "model", "data")),
    # fallback: replicate
    (r".*",                      ()),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def spec_for(path_str: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    for pattern, template in _RULES:
        if re.search(pattern, path_str):
            axes = []
            # align template to the trailing dims (stacked leading dims may
            # be absent in unstacked params)
            tpl = template[-len(shape):] if template else ()
            tpl = ("_",) * (len(shape) - len(tpl)) + tuple(tpl)
            for dim, ax in zip(shape, tpl):
                if ax == "_" or ax not in mesh.shape:
                    axes.append(None)
                elif dim % _axis_size(mesh, ax) == 0 and dim >= _axis_size(mesh, ax):
                    axes.append(ax)
                else:
                    # pjit arguments require even sharding; dims that don't
                    # divide (small kv-head counts etc.) stay replicated.
                    # Large uneven dims are avoided by construction (vocab is
                    # padded to 128 in the embedding layer).
                    axes.append(None)
            # drop trailing Nones for a tidy spec
            while axes and axes[-1] is None:
                axes.pop()
            return P(*axes)
    return P()


def param_shardings(params, mesh: Mesh, fsdp: bool = True):
    """Pytree of NamedShardings matching ``params``' structure.

    ``fsdp=False`` drops the data-axis (ZeRO) sharding — weights are
    TP-sharded only and replicated across data. The serving layout: at
    batch-bound decode the per-step FSDP weight gathers dominate the
    collective term, while TP-only weights fit comfortably in bf16."""
    def leaf(path, x):
        spec = spec_for(_path_str(path), x.shape, mesh)
        if not fsdp:
            spec = P(*[None if a == "data" else a for a in spec])
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(leaf, params)


def param_specs(params, mesh: Mesh):
    def leaf(path, x):
        return spec_for(_path_str(path), x.shape, mesh)
    return jax.tree_util.tree_map_with_path(leaf, params)


# ------------------------------------------------------------- activations --

def batch_axes(mesh: Mesh):
    """The data-parallel mesh axes (pod extends data when present)."""
    return (("pod", "data") if "pod" in mesh.shape else ("data",))


def batch_spec(mesh: Mesh) -> P:
    return P(batch_axes(mesh))


def token_sharding(mesh: Mesh, ndim: int = 2,
                   batch_size: int | None = None) -> NamedSharding:
    """(B, S[, ...]) activations: batch over the DP axes. If ``batch_size``
    is given and doesn't divide the DP degree (long_500k's batch of 1), the
    input stays replicated."""
    dp = batch_axes(mesh)
    if batch_size is not None:
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if batch_size % dp_size or batch_size < dp_size:
            return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(dp, *([None] * (ndim - 1))))


def logits_sharding(mesh: Mesh, ndim: int, batch_size: int,
                    vocab: int) -> NamedSharding:
    """(B, [S,] V) logits: batch over DP, padded vocab over model."""
    dp = batch_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    model = mesh.shape.get("model", 1)
    axes: list = [None] * ndim
    if batch_size % dp_size == 0 and batch_size >= dp_size:
        axes[0] = dp
    if vocab % model == 0 and vocab >= model:
        axes[-1] = "model"
    return NamedSharding(mesh, P(*axes))


def cache_sharding(mesh: Mesh, cache_shape: tuple[int, ...],
                   kv_heads_axis: int = 3,
                   prefer: str = "seq") -> NamedSharding:
    """KV-cache (L, B, T, H_kv, hd): batch over data; the model axis takes
    either the time dim (``prefer='seq'`` — context-parallel cache, default:
    per-device residency T/model, per-layer gathers) or the kv-heads dim
    (``prefer='heads'`` — zero attention collectives but full-T residency);
    whichever the preferred dim doesn't divide falls back to the other."""
    dp = batch_axes(mesh)
    model = mesh.shape.get("model", 1)
    axes: list = [None] * len(cache_shape)
    b = cache_shape[1]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if b % dp_size == 0 and b >= dp_size:
        axes[1] = dp
    if len(cache_shape) > kv_heads_axis:
        h = cache_shape[kv_heads_axis]
        t = cache_shape[2]
        t_ok = t % model == 0 and t >= model
        h_ok = h % model == 0 and h >= model
        if prefer == "heads" and h_ok:
            axes[kv_heads_axis] = "model"
        elif t_ok:
            axes[2] = "model"
        elif h_ok:
            axes[kv_heads_axis] = "model"
    while axes and axes[-1] is None:
        axes.pop()
    return NamedSharding(mesh, P(*axes))


def cache_shardings(cache, mesh: Mesh, prefer: str = "seq"):
    """Shardings for a cache pytree (decode/serve path)."""
    def leaf(path, x):
        name = _path_str(path)
        if name.split("/")[-1] in ("k", "v", "ck", "cv"):
            return cache_sharding(mesh, x.shape, prefer=prefer)
        # recurrent states: (L, B, ...) — batch over data, last dim model
        axes: list = [None] * x.ndim
        dp = batch_axes(mesh)
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if x.ndim >= 2 and x.shape[1] % dp_size == 0 and x.shape[1] >= dp_size:
            axes[1] = dp
        model = mesh.shape.get("model", 1)
        if x.ndim >= 3 and x.shape[-1] % model == 0 and x.shape[-1] >= model:
            axes[-1] = "model"
        return NamedSharding(mesh, P(*axes))
    return jax.tree_util.tree_map_with_path(leaf, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
