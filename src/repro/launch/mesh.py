"""Production mesh + sharding recipes.

Single pod : (data=16, model=16)            = 256 v5e chips
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

Recipes:
  'tp'      — base weights tensor-parallel over 'model'; replicated over
              'data'. For models whose weights fit per device (<~20B).
  'fsdp_tp' — 2D: the complementary weight dim additionally sharded over
              'data' (and 'pod'); GSPMD inserts the gather/reduce
              collectives. Required for the 104B/235B/400B configs.

Every rule degrades gracefully: an axis is only applied when the dimension
is divisible by the mesh-axis size (e.g. whisper's vocab 51865 stays
replicated instead of failing to lower).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def wants_fsdp(cfg) -> bool:
    """2D-shard base weights when they cannot fit one device replicated over
    'data' (bf16 bytes / model-axis > ~8GB)."""
    return cfg.n_param_estimate() * 2 / 16 > 8e9


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

# matrices laid out (..., d_in, d_out): shard d_out over model, d_in over fsdp
_IN_OUT = {"wq", "wk", "wv", "wi", "wg", "in_proj", "cm_wk", "w_dt", "wr",
           "lm_head"}
# matrices laid out (..., d_out_model_sharded, d_in): transpose-flavoured
_OUT_IN = {"wo", "wd", "out_proj", "cm_wv"}


def _spec_for(path_names, shape, fsdp_axes):
    """PartitionSpec for one base-weight leaf, by name + rank."""
    name = path_names[-1]
    under_moe = "moe" in path_names
    nd = len(shape)

    def lead(n):
        return (None,) * n

    if name == "embed":
        return ("model", fsdp_axes)
    if name == "router":
        return lead(nd - 2) + (fsdp_axes, "model")
    # experts over model; d_model over data. (§Perf-3 iter 2 REFUTED the
    # F-over-data variant: with clients/tokens sharded on 'data', any other
    # placement forces per-chunk gathers of the dispatch tensors — measured
    # 1.96TB -> 3.85TB/dev on qwen3 train_4k. D-over-data is the best
    # single-program layout; the next structural step would be
    # all-to-all token exchange (Megatron-MoE), see EXPERIMENTS §Perf-3.)
    if under_moe and name in ("wi", "wg") and nd == 4:
        return (None, "model", fsdp_axes, None)
    if under_moe and name == "wd" and nd == 4:
        return (None, "model", None, fsdp_axes)
    if name in _IN_OUT:
        return lead(nd - 2) + (fsdp_axes, "model")
    if name in _OUT_IN:
        return lead(nd - 2) + ("model", fsdp_axes)
    if name == "conv_w":
        return lead(nd - 1) + ("model",)
    if name in ("w_b", "w_c"):
        return lead(nd - 2) + (fsdp_axes, None)
    if name == "w_lora_a":
        return lead(nd - 2) + (fsdp_axes, None)
    if name == "w_lora_b":
        return lead(nd - 2) + (None, "model")
    return lead(nd)   # norms, biases, scalars, mu/u/w0 vectors: replicated


def _prune_indivisible(spec, shape, mesh):
    out = []
    for axes, dim in zip(spec, shape):
        if axes is None:
            out.append(None)
            continue
        if axis_size(mesh, axes) == 0 or dim % axis_size(mesh, axes) != 0:
            out.append(None)
        else:
            out.append(axes)
    return P(*out)


def base_shardings(cfg, mesh, base_tree):
    """NamedSharding tree for the frozen base weights."""
    fsdp = data_axes(mesh) if wants_fsdp(cfg) else None
    fsdp = fsdp if fsdp is None else (fsdp[0] if len(fsdp) == 1 else fsdp)

    def one(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        spec = _spec_for(names, leaf.shape, fsdp)
        spec = _prune_indivisible(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, base_tree)


def replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# ---------------------------------------------------------------------------
# Batch / cache shardings
# ---------------------------------------------------------------------------

def train_batch_shardings(mesh, batch_tree):
    """Client axis (leading) over ('pod','data')."""
    d = data_axes(mesh)
    d = d[0] if len(d) == 1 else d

    def one(leaf):
        spec = [d] + [None] * (leaf.ndim - 1)
        return NamedSharding(mesh, _prune_indivisible(spec, leaf.shape, mesh))

    return jax.tree.map(one, batch_tree)


def serve_batch_shardings(mesh, batch_tree):
    return train_batch_shardings(mesh, batch_tree)   # batch-leading too


def cache_shardings(cfg, mesh, cache_tree):
    """Caches: batch dim -> data axes; the long 'sequence-like' dim (KV
    positions / conv taps) or head dim -> 'model' when divisible."""
    d = data_axes(mesh)
    d = d[0] if len(d) == 1 else d

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        nd = leaf.ndim
        if name in ("k", "v", "attn_k", "attn_v", "k_scale", "v_scale"):
            spec = [None, d, "model", None, None][:nd]
        elif name == "wkv":        # (L,B,H,hd,hd)
            spec = [None, d, "model", None, None]
        elif name == "ssm":        # (L,B,H,hd,N)
            spec = [None, d, "model", None, None]
        elif name == "conv":       # (L,B,K-1,d_inner)
            spec = [None, d, None, "model"]
        elif name in ("shift_tm", "shift_cm"):   # (L,B,1,D)
            spec = [None, d, None, "model"]
        elif name == "memory":     # (B,F,D)
            spec = [d, None, None]
        else:
            spec = [None] * nd
        return NamedSharding(mesh, _prune_indivisible(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, cache_tree)
