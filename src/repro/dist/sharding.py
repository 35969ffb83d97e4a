"""Mesh / NamedSharding helpers shared by the launchers and dry-run cells.

Three groups:

  * `shard_map` — `jax.shard_map` with the replication check off; every
    shard_map in this repo goes through here.
  * spec trees — `lm_param_specs` / `opt_specs` / ... return PartitionSpec
    pytrees that mirror the corresponding parameter pytrees (dense parts
    tensor-parallel over `tp`, embeddings row-sharded, MoE expert-sharded).
  * materialization — `to_shardings` / `abstract_with_sharding` turn spec
    trees into NamedSharding / ShapeDtypeStruct trees for jit in/out specs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import LMConfig, RecSysConfig


# ----------------------------------------------------------------- shard_map
def shard_map(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with `check_vma=check` (off by default)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# ----------------------------------------------------------------- utilities
def dp_entry(dp: Tuple[str, ...]):
    """A PartitionSpec entry for the (possibly multi-axis) data dimension."""
    if not dp:
        return None
    return dp[0] if len(dp) == 1 else tuple(dp)


def to_shardings(mesh: Mesh, specs):
    """PartitionSpec tree -> NamedSharding tree (for jit out_shardings)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def abstract_with_sharding(tree, mesh: Mesh, specs):
    """Attach NamedShardings to a ShapeDtypeStruct tree (dry-run inputs)."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)


# ------------------------------------------------------------------ LM specs
def lm_param_specs(cfg: LMConfig, dp: Tuple[str, ...],
                   tp: Optional[str]) -> Dict[str, Any]:
    """PartitionSpec tree mirroring `transformer.init_lm` params.

    Megatron-style: qkv/ffn-in column-parallel over `tp`, wo/ffn-out
    row-parallel, embedding row-sharded (vocab), MoE expert-sharded.
    """
    layer = {
        "ln_attn": P(None, None),
        "wq": P(None, None, tp),
        "wk": P(None, None, tp),
        "wv": P(None, None, tp),
        "wo": P(None, tp, None),
        "ln_ffn": P(None, None),
    }
    if cfg.moe:
        layer["moe"] = {
            "router": P(None, None, None),
            "w_in": P(None, tp, None, None),
            "w_out": P(None, tp, None, None),
        }
        if cfg.gated:
            layer["moe"]["w_gate"] = P(None, tp, None, None)
    else:
        layer["ffn"] = {"w_in": P(None, None, tp), "w_out": P(None, tp, None)}
        if cfg.gated:
            layer["ffn"]["w_gate"] = P(None, None, tp)
    specs = {"embed": P(tp, None), "layers": layer, "ln_out": P(None)}
    if not cfg.tie_embeddings:
        specs["head"] = P(None, tp)
    return specs


def lm_batch_specs(dp: Tuple[str, ...]) -> Dict[str, P]:
    d = dp_entry(dp)
    return {"tokens": P(d, None), "labels": P(d, None)}


def lm_cache_specs(cfg: LMConfig, batch: int, dp: Tuple[str, ...],
                   tp: Optional[str], dp_size: int) -> Dict[str, P]:
    """KV-cache specs [L, B, S, n_kv, d_head]: batch over dp when it divides,
    kv heads over tp when they divide (else replicated)."""
    d = dp_entry(dp) if batch >= max(dp_size, 1) else None
    return {"k": P(None, d, None, None, None),
            "v": P(None, d, None, None, None),
            "len": P(d)}


# -------------------------------------------------------------- recsys specs
def recsys_param_specs(cfg: RecSysConfig, dp: Tuple[str, ...],
                       tp: Optional[str]) -> Dict[str, Any]:
    """AutoInt params: embedding table row-sharded over `tp` (the lookup
    shard_maps over it), attention projections replicated."""
    layer = {"wq": P(None, None), "wk": P(None, None),
             "wv": P(None, None), "wr": P(None, None)}
    return {"table": P(tp, None),
            "layers": [layer for _ in range(cfg.n_attn_layers)],
            "final": P(None, None), "final_b": P(None)}


# ------------------------------------------------------------ optimizer state
def opt_specs(param_specs):
    """AdamW state (step, m, v): moments shard like their parameters."""
    from repro.optim.adamw import AdamWState
    return AdamWState(step=P(),
                      m=jax.tree.map(lambda s: s, param_specs,
                                     is_leaf=lambda x: isinstance(x, P)),
                      v=jax.tree.map(lambda s: s, param_specs,
                                     is_leaf=lambda x: isinstance(x, P)))
