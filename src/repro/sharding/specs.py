"""Sharding policy: mesh-axis assignment for parameters, activations, caches.

Policy (MaxText-style hybrid):
- ``pod``   — pure data parallelism across pods (DCN): batch only.
- ``data``  — within-pod data parallelism + FSDP (ZeRO-3): batch AND the
  d_model dim of every weight matrix.
- ``model`` — tensor parallelism: attention heads / d_ff / experts / vocab.

Dims that do not divide the axis size are left unsharded (the policy is
divisibility-aware; e.g. hymba's 25 heads stay replicated over ``model``
while its d_ff=5504 is sharded 16-way). Long-context decode cells shard the
KV-cache *sequence* dim instead (set ``cache_seq_axes``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh
    batch_axes: Tuple[str, ...]           # ('data',) or ('pod','data')
    fsdp_axis: Optional[str] = "data"
    model_axis: str = "model"
    # decode-cache sequence sharding (e.g. ('model',) or ('data','model'))
    cache_seq_axes: Optional[Tuple[str, ...]] = None
    # decode-optimised MoE: never gather expert weights (see models/moe.py)
    moe_weight_stationary: bool = False
    # q-block-parallel attention when heads don't divide the model axis
    attn_qblock: bool = False
    # sLSTM: accumulate recurrent-weight grads locally, one trailing psum
    slstm_local_grad: bool = False

    # -- helpers ------------------------------------------------------------
    def _axsz(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return int(np.prod([self.mesh.shape[a] for a in axes]))

    def div(self, n: int, axes) -> bool:
        s = self._axsz(axes)
        return s > 0 and n % s == 0

    def maybe(self, n: int, axes):
        """axes if n divides evenly over them, else None."""
        return axes if self.div(n, axes) else None

    def _c(self, x, spec):
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh,
                                                                 spec))

    def attn_layout(self, n_heads: int, n_kv: int) -> str:
        """'grouped' when KV heads shard evenly; 'expand' (KV replication up
        to n_heads) when only Q heads do; else 'qblock' (query-block
        sharding) when enabled, or 'grouped' (replicated attention,
        documented imbalance)."""
        if self.model_axis is None:
            return "grouped"
        if self.div(n_kv, self.model_axis):
            return "grouped"
        if self.div(n_heads, self.model_axis):
            return "expand"
        return "qblock" if self.attn_qblock else "grouped"

    def act_qblocks(self, x):
        """(B, nb, Bq, K, G, d): shard the query-block dim over model."""
        b = self.maybe(x.shape[0], self.batch_axes)
        n = self.maybe(x.shape[1], self.model_axis)
        return self._c(x, P(b, n, None, None, None, None))

    # -- activation constraints ----------------------------------------------
    def act_btd(self, x):
        b = self.maybe(x.shape[0], self.batch_axes)
        return self._c(x, P(b, None, None))

    def act_ff(self, x):
        b = self.maybe(x.shape[0], self.batch_axes)
        f = self.maybe(x.shape[-1], self.model_axis)
        return self._c(x, P(b, None, f))

    def act_logits(self, x):
        b = self.maybe(x.shape[0], self.batch_axes)
        v = self.maybe(x.shape[-1], self.model_axis)
        return self._c(x, P(b, None, v))

    def act_kv(self, x):
        """(B, S, K, hd) KV tensors / caches, or grouped q (B,S,K,G,hd)."""
        b = self.maybe(x.shape[0], self.batch_axes)
        if x.ndim == 5:  # grouped q (B, S, K, G, hd): shard K if divisible
            kk = self.maybe(x.shape[2], self.model_axis)
            return self._c(x, P(b, None, kk, None, None))
        kk = self.maybe(x.shape[2], self.model_axis)
        if kk is None and self.cache_seq_axes is not None \
                and self.div(x.shape[1], self.cache_seq_axes):
            return self._c(x, P(b, self.cache_seq_axes, None, None))
        return self._c(x, P(b, None, kk, None))

    def batch_spec(self, batch_shape_tree):
        """Input-batch shardings (tokens/labels/embeds)."""
        def one(sds):
            b = self.maybe(sds.shape[0], self.batch_axes)
            return NamedSharding(self.mesh,
                                 P(*([b] + [None] * (len(sds.shape) - 1))))
        return jax.tree_util.tree_map(one, batch_shape_tree)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

_IN_OUT = {  # name -> (in-dim index from the right, shard out dim on model?)
    "wq": True, "wk": True, "wv": True, "wi": True, "wg": True, "w_in": True,
    "w": True, "wog": True, "wo": False, "w_out": False,
}


def _leaf_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               cfg: ModelConfig, ctx: ShardCtx) -> P:
    """PartitionSpec for one parameter leaf, prefixing stack dims with None."""
    name = path[-1]
    fs, mx = ctx.fsdp_axis, ctx.model_axis
    moe = "moe" in path
    nd = len(shape)

    def pad(spec_tail):
        return P(*([None] * (nd - len(spec_tail)) + list(spec_tail)))

    if name in ("embed", "unembed"):
        v = ctx.maybe(shape[0], mx)
        d = ctx.maybe(shape[1], fs) if fs else None
        return P(v, d)
    if name == "router":
        return pad([ctx.maybe(shape[-2], fs), None])
    if moe and name in ("wi", "wg"):
        if cfg.moe.parallel_mode == "ep" and \
                ctx.div(cfg.moe.num_experts, mx):
            return pad([mx, ctx.maybe(shape[-2], fs), None])
        return pad([None, ctx.maybe(shape[-2], fs),
                    ctx.maybe(shape[-1], mx)])
    if moe and name == "wo":
        if cfg.moe.parallel_mode == "ep" and \
                ctx.div(cfg.moe.num_experts, mx):
            return pad([mx, None, ctx.maybe(shape[-1], fs)])
        return pad([None, ctx.maybe(shape[-2], mx),
                    ctx.maybe(shape[-1], fs)])
    if nd >= 2 and name in _IN_OUT:
        if _IN_OUT[name]:   # (..., D_in, D_out): FSDP in, TP out
            return pad([ctx.maybe(shape[-2], fs), ctx.maybe(shape[-1], mx)])
        return pad([ctx.maybe(shape[-2], mx), ctx.maybe(shape[-1], fs)])
    if name == "a_log":
        return pad([ctx.maybe(shape[-2], mx), None])
    if name == "conv_w":
        return pad([None, ctx.maybe(shape[-1], mx)])
    if name == "r":      # slstm recurrent (4, H, dh, dh)
        return pad([None, None, None])
    # norms, biases, gates, scalars
    return P(*([None] * nd))


def param_specs(params_tree, cfg: ModelConfig, ctx: ShardCtx):
    """PartitionSpec pytree matching a params (or ShapeDtypeStruct) pytree."""
    def walk(path, leaf):
        names = tuple(getattr(k, "key", getattr(k, "idx", "")) for k in path)
        names = tuple(str(n) for n in names)
        return _leaf_spec(names, leaf.shape, cfg, ctx)

    return jax.tree_util.tree_map_with_path(walk, params_tree)


def param_shardings(params_tree, cfg: ModelConfig, ctx: ShardCtx):
    specs = param_specs(params_tree, cfg, ctx)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def replica_device_groups(mesh: Mesh, axis: str = "data"):
    """Split a mesh's device grid into per-replica device groups along one
    named axis: replica ``i`` gets the (flattened) devices of slice ``i``.

    Serving maps one engine replica per slice
    (``repro.serve.EngineGroup.from_mesh``); the remaining axes stay
    available for intra-replica parallelism, and a replica whose slice
    holds several devices round-robins batches within it.
    """
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no axis {axis!r} (axes: {tuple(mesh.axis_names)})")
    ax = tuple(mesh.axis_names).index(axis)
    grid = np.moveaxis(np.asarray(mesh.devices), ax, 0)
    return [list(grid[i].ravel()) for i in range(grid.shape[0])]


def cache_shardings(cache_tree, cfg: ModelConfig, ctx: ShardCtx):
    """Shardings for the decode cache tree."""
    mx = ctx.model_axis

    def one(sds):
        shp = sds.shape
        nd = len(shp)
        # per-row prompt starts: (B,)
        if nd == 1:
            return NamedSharding(ctx.mesh,
                                 P(ctx.maybe(shp[0], ctx.batch_axes)))
        # attention caches: (..., B, S, K, hd)
        if nd >= 4 and shp[-1] == cfg.head_dim and shp[-2] == cfg.n_kv_heads:
            b = ctx.maybe(shp[-4], ctx.batch_axes)
            k = ctx.maybe(cfg.n_kv_heads, mx)
            s = None
            if k is None and ctx.cache_seq_axes is not None and \
                    ctx.div(shp[-3], ctx.cache_seq_axes):
                s = ctx.cache_seq_axes
            return NamedSharding(
                ctx.mesh, P(*([None] * (nd - 4) + [b, s, k, None])))
        # ssm / xlstm states: shard the widest trailing dim if divisible
        tail = ctx.maybe(shp[-1], mx) if shp[-1] >= 128 else None
        spec = [None] * nd
        spec[-1] = tail
        # batch dim heuristics: first dim after stack dims with |dim|>=dp
        return NamedSharding(ctx.mesh, P(*spec))

    return jax.tree_util.tree_map(one, cache_tree)
