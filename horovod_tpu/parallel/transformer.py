"""Explicitly-parallel GPT: 4-D (dp × sp × tp × ep) training step.

The framework's flagship distributed-training path, composing every
explicit-collective building block over one mesh:

* ``dp`` — data parallelism: fused gradient allreduce
  (:func:`horovod_tpu.ops.fusion.fused_allreduce`), the Horovod-parity
  core (reference ``DistributedOptimizer``).
* ``sp`` — sequence/context parallelism: ring attention
  (:func:`horovod_tpu.parallel.sp.ring_attention`) with K/V blocks
  rotating on nearest-neighbor ICI links; long context is O(S/n_sp)
  memory per device.
* ``tp`` — Megatron tensor parallelism: column/row parallel projections
  (:func:`horovod_tpu.parallel.tp`), one psum per attention block and one
  per MLP.
* ``ep`` — expert parallelism (``moe_experts > 0``): every FFN becomes a
  top-1 Switch MoE (:func:`horovod_tpu.parallel.ep.switch_moe_stacked`)
  with experts sharded over the **dp** axis — tokens ride ``all_to_all``
  to their expert's device, no extra replica axis is paid for, and
  expert gradients skip the dp allreduce (DeepSpeed-MoE layout).

Gradient synchronization needs exactly one fused psum over ``(dp, sp)``:
TP-sharded params get complete shard-gradients from local autodiff (the
activation psums' transpose rules handle the cross-shard terms), and
replicated params see identical gradients across ``tp`` — the Megatron
invariant, kept here by construction.

Layers are stacked and iterated with ``lax.scan`` (+ optional per-layer
``jax.checkpoint``) so compile time and HBM stay flat in depth.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import remat as _remat
from ..ops.fusion import fused_allreduce
from ..ops.collectives import Sum
from .ep import switch_moe_stacked
from .sp import ring_attention
from .tp import row_parallel


@dataclasses.dataclass(frozen=True)
class ParallelGPTConfig:
    vocab_size: int = 512
    max_len: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512
    dtype: Any = jnp.bfloat16
    # Per-layer remat of the scanned block: False/'none', True/'full', a
    # named jax.checkpoint_policies policy ('dots_saveable', ...) or a
    # custom policy callable (ops/remat.resolve_policy semantics — the
    # same knob the DP zoo and make_train_step(remat=...) share).
    remat: Any = True
    dp_axis: str = "dp"
    sp_axis: str = "sp"
    tp_axis: str = "tp"
    # Expert parallelism (4th dimension): > 0 turns every block's FFN into
    # a top-1 MoE with this many experts, sharded over the dp axis —
    # tokens all_to_all to their expert's device (DeepSpeed-MoE layout, so
    # no extra replica axis is paid for). Expert grads are complete from
    # local autodiff and skip the dp allreduce.
    moe_experts: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ep_axis(self) -> str:
        return self.dp_axis


def init_params(cfg: ParallelGPTConfig, key) -> Dict[str, jax.Array]:
    """Full (unsharded) parameter pytree; layer dims stacked on axis 0."""
    k = iter(jax.random.split(key, 16))
    init = lambda kk, *shape: (  # noqa: E731
        jax.random.normal(kk, shape, jnp.float32) * 0.02
    )
    L, D, H, hd, F = (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
    )
    params = {
        "wte": init(next(k), cfg.vocab_size, D),
        "wpe": init(next(k), cfg.max_len, D),
        "ln1_scale": jnp.ones((L, D)),
        "ln1_bias": jnp.zeros((L, D)),
        "wq": init(next(k), L, D, H, hd),
        "wk": init(next(k), L, D, H, hd),
        "wv": init(next(k), L, D, H, hd),
        "wo": init(next(k), L, H, hd, D),
        "ln2_scale": jnp.ones((L, D)),
        "ln2_bias": jnp.zeros((L, D)),
        "lnf_scale": jnp.ones((D,)),
        "lnf_bias": jnp.zeros((D,)),
    }
    if cfg.moe_experts:
        E = cfg.moe_experts
        params.update(
            {
                "gate": init(next(k), L, D, E),
                "moe_up": init(next(k), L, E, D, F),
                "moe_down": init(next(k), L, E, F, D),
            }
        )
    else:
        params.update(
            {
                "w_up": init(next(k), L, D, F),
                "b_up": jnp.zeros((L, F)),
                "w_down": init(next(k), L, F, D),
                "b_down": jnp.zeros((L, D)),
            }
        )
    return params


def param_specs(cfg: ParallelGPTConfig) -> Dict[str, P]:
    """shard_map in_specs: heads/d_ff over tp, experts over ep (= dp),
    rest replicated."""
    tp = cfg.tp_axis
    specs = {
        "wte": P(),
        "wpe": P(),
        "ln1_scale": P(),
        "ln1_bias": P(),
        "wq": P(None, None, tp, None),
        "wk": P(None, None, tp, None),
        "wv": P(None, None, tp, None),
        "wo": P(None, tp, None, None),
        "ln2_scale": P(),
        "ln2_bias": P(),
        "lnf_scale": P(),
        "lnf_bias": P(),
    }
    if cfg.moe_experts:
        ep = cfg.ep_axis
        specs.update(
            {
                "gate": P(),
                "moe_up": P(None, ep, None, tp),
                "moe_down": P(None, ep, tp, None),
            }
        )
    else:
        specs.update(
            {
                "w_up": P(None, None, tp),
                "b_up": P(None, tp),
                "w_down": P(None, tp, None),
                "b_down": P(),
            }
        )
    return specs


def _ln(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def forward_with_aux(params, tokens, cfg: ParallelGPTConfig):
    """Per-device forward. ``tokens``: ``[B_local, S_local]`` (batch sharded
    over dp, sequence over sp; params pre-sharded per :func:`param_specs`).
    Returns ``(fp32 logits [B_local, S_local, vocab], aux_loss)`` — aux is
    the summed MoE load-balancing loss (0 for dense configs).
    """
    sp, tp = cfg.sp_axis, cfg.tp_axis
    r_sp = lax.axis_index(sp)
    b, s = tokens.shape
    dt = cfg.dtype

    pos = r_sp * s + jnp.arange(s)
    x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[pos]

    def ffn_dense(h, lp):
        up = jax.nn.gelu(
            jnp.einsum("bsd,df->bsf", h, lp["w_up"].astype(dt))
            + lp["b_up"].astype(dt)
        )
        down = row_parallel(
            up, lp["w_down"].astype(dt), axis=tp, bias=lp["b_down"].astype(dt)
        )
        return down, jnp.zeros((), jnp.float32)

    def ffn_moe(h, lp):
        bb, ss, d = h.shape

        def expert_fn(ep_params, toks):
            # toks [e_local, G, D]; tp column/row parallel inside each
            # expert: up is tp-sharded on F, down psums over tp.
            up_w, down_w = ep_params
            hh = jax.nn.gelu(
                jnp.einsum("egd,edf->egf", toks, up_w.astype(dt))
            )
            return lax.psum(
                jnp.einsum("egf,efd->egd", hh, down_w.astype(dt)), tp
            )

        out, aux = switch_moe_stacked(
            h.reshape(bb * ss, d),
            lp["gate"],
            expert_fn,
            (lp["moe_up"], lp["moe_down"]),
            axis=cfg.ep_axis,
            capacity_factor=cfg.capacity_factor,
        )
        return out.reshape(bb, ss, d), aux

    def block(carry, lp):
        x, aux_acc = carry
        h = _ln(x, lp["ln1_scale"], lp["ln1_bias"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(dt))
        kk = jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(dt))
        a = ring_attention(q, kk, v, axis=sp, causal=True)
        # Row-parallel out projection: partial sums over local heads, one
        # psum over tp.
        y = lax.psum(jnp.einsum("bshk,hkd->bsd", a, lp["wo"].astype(dt)), tp)
        x = x + y
        h = _ln(x, lp["ln2_scale"], lp["ln2_bias"])
        ff, aux = (ffn_moe if cfg.moe_experts else ffn_dense)(h, lp)
        return (x + ff, aux_acc + aux), None

    layer_params = {
        k: v
        for k, v in params.items()
        if k not in ("wte", "wpe", "lnf_scale", "lnf_bias")
    }
    blk = _remat.checkpoint_fn(block, cfg.remat)
    (x, aux), _ = lax.scan(blk, (x, jnp.zeros((), jnp.float32)), layer_params)
    x = _ln(x, params["lnf_scale"], params["lnf_bias"])
    logits = x.astype(jnp.float32) @ params["wte"].T.astype(jnp.float32)
    return logits, aux


def forward(params, tokens, cfg: ParallelGPTConfig):
    """Logits-only forward (see :func:`forward_with_aux`)."""
    return forward_with_aux(params, tokens, cfg)[0]


def loss_fn(params, tokens, cfg: ParallelGPTConfig):
    """Next-token CE, exact across the sp sharding.

    Labels shift across shard boundaries: each device fetches its
    successor's first token via ``ppermute`` (the cross-shard halo); the
    final global position is masked.
    """
    sp = cfg.sp_axis
    n_sp = int(lax.axis_size(sp))
    r_sp = lax.axis_index(sp)
    b, s = tokens.shape

    logits, aux = forward_with_aux(params, tokens, cfg)
    nxt = lax.ppermute(
        tokens[:, :1], sp, [(i, (i - 1) % n_sp) for i in range(n_sp)]
    )
    labels = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
    pos = r_sp * s + jnp.arange(s)
    valid = (pos < n_sp * s - 1).astype(jnp.float32)[None, :]

    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    local_sum = jnp.sum(ce * valid)
    local_cnt = jnp.sum(valid) * b
    total = lax.psum(
        jnp.stack([local_sum, local_cnt]), (cfg.dp_axis, sp)
    )
    loss = total[0] / total[1]
    if cfg.moe_experts:
        # aux already pmean'ed over ep(=dp) per layer; average the sp
        # shards' (different-token) estimates too.
        loss = loss + cfg.aux_loss_weight * lax.pmean(aux, sp)
    return loss


def make_parallel_train_step(
    cfg: ParallelGPTConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    donate: bool = True,
):
    """Build the jitted 3-D train step (see module docstring).

    ``opt_state`` sharding mirrors the parameter sharding (optax states
    are param-shaped pytrees; scalar leaves are replicated).
    """
    specs = param_specs(cfg)
    tok_spec = P(cfg.dp_axis, cfg.sp_axis)
    opt_specs = opt_state_specs(cfg, optimizer)

    def _step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
        if cfg.moe_experts:
            # Expert params are sharded over ep (= dp): their gradients
            # come back complete through the all_to_all transpose, so they
            # must NOT be summed over dp — only over the sp replicas
            # (DeepSpeed-MoE convention). Derived from the sharding specs
            # so new ep-sharded params can't silently miss the exemption.
            moe_keys = {k for k, s in specs.items() if cfg.ep_axis in s}
            dense = {k: v for k, v in grads.items() if k not in moe_keys}
            moe = {k: grads[k] for k in moe_keys}
            dense = fused_allreduce(
                dense, op=Sum, axis=(cfg.dp_axis, cfg.sp_axis)
            )
            moe = fused_allreduce(moe, op=Sum, axis=(cfg.sp_axis,))
            grads = {**dense, **moe}
        else:
            grads = fused_allreduce(
                grads, op=Sum, axis=(cfg.dp_axis, cfg.sp_axis)
            )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    mapped = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(specs, opt_specs, tok_spec),
        out_specs=(specs, opt_specs, P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())


def opt_state_specs(cfg: ParallelGPTConfig, optimizer):
    """Opt-state sharding specs, derived structurally: optimizer states
    (Adam moments etc.) mirror the params dict, so any opt-state leaf
    whose path ends in a known param name inherits that param's spec;
    scalar counters and other leaves are replicated. (Keyed by path, not
    shape — distinct params can share a shape, e.g. d_model == d_ff.)"""
    specs = param_specs(cfg)
    params_shape = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)

    def leaf_spec(path, leaf):
        for entry in reversed(path):
            key = getattr(entry, "key", None)
            if key in specs:
                return specs[key]
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, opt_shape)


def shard_init(cfg: ParallelGPTConfig, mesh: Mesh, key, optimizer):
    """Initialize params + opt state directly onto the mesh."""
    from jax.sharding import NamedSharding

    specs = param_specs(cfg)
    params = init_params(cfg, key)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    opt_state = optimizer.init(params)
    return params, opt_state


def shard_state(cfg: ParallelGPTConfig, mesh: Mesh, params, opt_state, optimizer):
    """Re-shard an existing (host-snapshot or device) params + opt_state
    onto ``mesh`` — the elastic rescale path: after a world-size change,
    a committed ``elastic.TrainState`` snapshot is restored onto the NEW
    mesh with the same sharding rules, preserving optimizer moments
    (re-initializing would lose them). The TPU analog of the reference's
    state broadcast after re-init (``horovod/common/elastic.py`` sync)."""
    from jax.sharding import NamedSharding

    import jax.numpy as jnp

    def put(tree, tree_specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(
                jnp.asarray(x), NamedSharding(mesh, s)
            ),
            tree,
            tree_specs,
        )

    return (
        put(params, param_specs(cfg)),
        put(opt_state, opt_state_specs(cfg, optimizer)),
    )
