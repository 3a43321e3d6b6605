"""Parameter partitioning rules: path-pattern -> PartitionSpec.

Strategy (DESIGN.md Section 5):
  * TP over "model": projection output features, expert axis (EP), vocab.
  * Optional FSDP/ZeRO over "data": the other large dim of each matrix
    (enabled for >=30B configs; moments/params shards congruent).
  * DP across "pod" (multi-pod): replicated params, batch-sharded acts.
Every rule checks divisibility against the actual mesh axis sizes and falls
back to replication rather than producing an invalid sharding.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    return mesh.shape[name]


def _ok(dim: int, mesh: Mesh, axis) -> bool:
    if axis is None:
        return True
    if isinstance(axis, tuple):
        size = int(np.prod([_axis_size(mesh, a) for a in axis]))
    else:
        size = _axis_size(mesh, axis)
    return dim % size == 0


def _spec(shape, mesh: Mesh, *axes):
    """Build a PartitionSpec, dropping any axis that doesn't divide."""
    cleaned = []
    for dim, ax in zip(shape, axes):
        cleaned.append(ax if _ok(dim, mesh, ax) else None)
    # trailing axes unspecified = replicated
    return P(*cleaned)


def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               fsdp: bool) -> P:
    """Sharding rule for one parameter leaf (path uses '/' separators).

    Stacked (scan) params carry a leading layer dim -> leading None.
    """
    dp = "data" if fsdp else None
    lead: Tuple = ()
    if "blocks/" in path:                # scanned stack: (L, ...)
        lead = (None,)
        shape = shape[1:]

    leaf = path.split("/")[-1]

    if leaf in ("embed",):               # (V, d)
        return _spec(lead + shape, mesh, *lead, "model", dp)
    if leaf in ("head",):                # (d, V)
        return _spec(lead + shape, mesh, *lead, dp, "model")
    if leaf in ("wq", "wk", "wv", "w_y", "w_u", "w_a", "w_x", "in_proj"):
        return _spec(lead + shape, mesh, *lead, dp, "model")
    if leaf in ("wo", "w_o", "out_proj"):
        return _spec(lead + shape, mesh, *lead, "model", dp)
    if leaf in ("gate", "up", "down"):
        if len(shape) == 3:              # MoE experts: (E, d, f)
            return _spec(lead + shape, mesh, *lead, "model", dp, None)
        if leaf == "down":               # (f, d)
            return _spec(lead + shape, mesh, *lead, "model", dp)
        return _spec(lead + shape, mesh, *lead, dp, "model")
    if leaf == "router":                 # (d, E): replicate E (small)
        return _spec(lead + shape, mesh, *lead, dp, None)
    if leaf == "conv_w" or shape == ():
        return P()
    if len(shape) == 1:                  # norms, biases, scalars
        return _spec(lead + shape, mesh, *lead, None)
    # default 2D: shard last dim on model if divisible
    return _spec(lead + shape, mesh, *lead, dp, "model")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def make_param_shardings(params_shape, mesh: Mesh, fsdp: bool):
    """Pytree of NamedShardings for an eval_shape'd params tree."""

    def one(path, leaf):
        spec = param_spec(_path_str(path), leaf.shape, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def make_state_shardings(state_shape, mesh: Mesh, fsdp: bool):
    """TrainState shardings: moments follow their parameters; step replicated."""

    def one(path, leaf):
        p = _path_str(path)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        # strip the TrainState prefix (params/..., opt/m/..., opt/v/...)
        parts = p.split("/")
        if parts[0] == "params":
            core = "/".join(parts[1:])
        elif parts[0] == "opt" and parts[1] in ("m", "v"):
            core = "/".join(parts[2:])
        else:
            core = p
        spec = param_spec(core, leaf.shape, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, state_shape)


# ---------------------------------------------------------------------------
# Activation rules + batch sharding
# ---------------------------------------------------------------------------

def activation_rules(mesh: Mesh, model_cfg: ModelConfig,
                     run_cfg: RunConfig) -> Dict[str, P]:
    """Named activation constraints consumed by sharding.api.shard().

    Divisibility-aware: head-sharded attention ("act_bshd") when n_heads
    divides the model axis, otherwise context-parallel k/v (sequence dim on
    "model"); MoE dispatch buffers expert-sharded (EP) when E divides.
    """
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    batch: Any = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    gb = run_cfg.global_batch
    bsize = int(np.prod([mesh.shape[a] for a in (batch_axes or ())]))
    if gb % max(bsize, 1):
        batch = "data" if gb % dict(mesh.shape).get("data", 1) == 0 else None
    tp = mesh.shape["model"] if "model" in mesh.shape else 1

    seq_ax = None
    if (run_cfg.seq_shard and run_cfg.mode == "train"
            and run_cfg.seq_len % max(tp, 1) == 0):
        seq_ax = "model"      # Megatron-SP: LN/residual segments S-sharded
    rules = {
        "act_btd": P(batch, seq_ax, None),
        "act_btv": P(batch, None, "model"),
    }
    if model_cfg.n_heads and model_cfg.n_heads % tp == 0:
        rules["act_q"] = P(batch, None, "model", None)
        rules["act_kv"] = P(batch, None, "model", None)
    else:
        # context parallelism: shard the sequence axis of k/v; q replicated
        # along heads (softmax/psum over the sharded kv axis is GSPMD's job)
        rules["act_q"] = P(batch, None, None, None)
        rules["act_kv"] = P(batch, "model", None, None)
    # decode KV cache: batch on data, sequence on model (flash-decode layout)
    rules["act_cache"] = P(batch, "model", None, None)
    if model_cfg.n_experts and model_cfg.n_experts % tp == 0:
        rules["act_ecd"] = P("model", None, None)
        # group-local MoE dispatch buffer (g, E, C, d): groups on the batch
        # axes, experts on the TP axis -> the EP all_to_all boundary.
        rules["act_gecd"] = P(batch, "model", None, None)
    return rules


def dp_group_count(mesh: Mesh, model_cfg: ModelConfig,
                   run_cfg: RunConfig) -> int:
    """Number of data-parallel groups for group-local MoE dispatch."""
    rules = activation_rules(mesh, model_cfg, run_cfg)
    batch = rules["act_btd"][0]
    if batch is None:
        return 1
    axes = batch if isinstance(batch, tuple) else (batch,)
    return int(np.prod([dict(mesh.shape)[a] for a in axes]))


def make_policy(mesh: Mesh, model_cfg: ModelConfig, run_cfg: RunConfig):
    """ShardingPolicy with activation rules + trace-time meta hints."""
    from repro.sharding.api import ShardingPolicy
    return ShardingPolicy(
        mesh=mesh,
        rules=activation_rules(mesh, model_cfg, run_cfg),
        meta={"dp_groups": dp_group_count(mesh, model_cfg, run_cfg)})


def batch_sharding(mesh: Mesh, model_cfg: ModelConfig,
                   run_cfg: RunConfig) -> NamedSharding:
    rules = activation_rules(mesh, model_cfg, run_cfg)
    return NamedSharding(mesh, rules["act_btd"])
