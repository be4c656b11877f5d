"""3-D (dp x sp x tp) parallel GPT tests: parity with a single-device
reference computation and end-to-end training."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.parallel.transformer import (
    ParallelGPTConfig,
    forward,
    init_params,
    loss_fn,
    make_parallel_train_step,
    param_specs,
    shard_init,
)


def _cfg(**kw):
    base = dict(
        vocab_size=64, max_len=64, d_model=32, n_heads=4, n_layers=2,
        d_ff=64, dtype=jnp.float32, remat=False,
    )
    base.update(kw)
    return ParallelGPTConfig(**base)


def _mesh222():
    devs = jax.devices("cpu")[:8]
    return mesh_lib.build_mesh({"dp": 2, "sp": 2, "tp": 2}, devices=devs)


def _reference_forward(params, tokens, cfg):
    """Single-device dense reference of the same math."""
    from horovod_tpu.parallel.transformer import _ln

    x = params["wte"][tokens] + params["wpe"][jnp.arange(tokens.shape[1])]
    L = cfg.n_layers
    for i in range(L):
        lp = {k: v[i] for k, v in params.items() if v.ndim and v.shape[0] == L}
        h = _ln(x, lp["ln1_scale"], lp["ln1_bias"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        from horovod_tpu.models.transformer import dot_product_attention

        a = dot_product_attention(q, k, v, causal=True)
        x = x + jnp.einsum("bshk,hkd->bsd", a, lp["wo"])
        h = _ln(x, lp["ln2_scale"], lp["ln2_bias"])
        up = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, lp["w_up"]) + lp["b_up"])
        x = x + jnp.einsum("bsf,fd->bsd", up, lp["w_down"]) + lp["b_down"]
    x = _ln(x, params["lnf_scale"], params["lnf_bias"])
    return x @ params["wte"].T


def test_parallel_forward_matches_dense():
    cfg = _cfg()
    mesh = _mesh222()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)

    expected = _reference_forward(params, tokens, cfg)

    mapped = jax.shard_map(
        lambda p, t: forward(p, t, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg), P("dp", "sp")),
        out_specs=P("dp", "sp"),
        check_vma=False,
    )
    out = mapped(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-4)


def test_parallel_loss_matches_dense():
    cfg = _cfg()
    mesh = _mesh222()
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)

    import optax as _optax

    logits = _reference_forward(params, tokens, cfg)
    ce = _optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]
    )
    expected = ce.mean()

    mapped = jax.shard_map(
        lambda p, t: loss_fn(p, t, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg), P("dp", "sp")),
        out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(
        float(mapped(params, tokens)), float(expected), rtol=2e-4
    )


def test_parallel_train_step_converges():
    cfg = _cfg()
    mesh = _mesh222()
    opt = optax.adam(1e-2)
    params, opt_state = shard_init(cfg, mesh, jax.random.PRNGKey(0), opt)
    step = make_parallel_train_step(cfg, opt, mesh)
    rng = np.random.RandomState(0)
    # A memorizable sequence pattern.
    tokens = jnp.asarray(
        np.tile(np.arange(32) % cfg.vocab_size, (4, 1)), jnp.int32
    )
    first = None
    for i in range(30):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first / 3, (first, float(loss))


def test_switch_moe_stacked_matches_dense_routing(world8):
    # e_local=2 experts/device over a 4-device axis == dense 8-expert
    # routing computed with the same per-shard capacity.
    from horovod_tpu.parallel.ep import switch_moe_stacked, top1_dispatch

    n, e_local, t, d = 8, 2, 16, 8
    e_total = n * e_local
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n * t, d), jnp.float32)
    gate = jnp.asarray(rng.randn(d, e_total), jnp.float32)
    w = jnp.asarray(rng.randn(e_total, d, d) * 0.3, jnp.float32)

    def expert_fn(wl, toks):
        # toks [e_local, G, D]; wl [e_local, D, D]
        return jnp.einsum("egd,edk->egk", jnp.tanh(toks), wl)

    mesh = hvd.context().mesh
    out = jax.shard_map(
        lambda xs, ws: switch_moe_stacked(
            xs, gate, expert_fn, ws, axis=hvd.WORLD_AXIS,
            capacity_factor=2.0,
        )[0],
        mesh=mesh,
        in_specs=(P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=P(hvd.WORLD_AXIS),
        check_vma=False,
    )(x, w)

    # Dense reference: per source shard, same dispatch; expert e sees the
    # concatenation of every shard's bin; outputs scattered back.
    capacity = int(np.ceil(t / e_total * 2.0))
    expected = np.zeros((n * t, d), np.float32)
    dispatches, combines = [], []
    for s in range(n):
        xs = x[s * t : (s + 1) * t]
        disp, comb, _ = top1_dispatch(np.asarray(xs) @ np.asarray(gate), capacity)
        dispatches.append(np.asarray(disp))
        combines.append(np.asarray(comb))
    for e in range(e_total):
        inp = np.concatenate(
            [
                np.einsum("tc,td->cd", dispatches[s][:, e, :], x[s * t : (s + 1) * t])
                for s in range(n)
            ]
        )  # [n*C, D]
        out_e = np.einsum(
            "gd,dk->gk", np.tanh(inp), np.asarray(w[e])
        ).reshape(n, capacity, d)
        for s in range(n):
            expected[s * t : (s + 1) * t] += np.einsum(
                "tc,cd->td", combines[s][:, e, :], out_e[s]
            )
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-4)


def test_moe_parallel_train_step_converges():
    cfg = _cfg(moe_experts=4, d_ff=64)
    mesh = _mesh222()
    opt = optax.adam(1e-2)
    params, opt_state = shard_init(cfg, mesh, jax.random.PRNGKey(0), opt)
    assert "moe_up" in params and "w_up" not in params
    step = make_parallel_train_step(cfg, opt, mesh)
    tokens = jnp.asarray(
        np.tile(np.arange(32) % cfg.vocab_size, (4, 1)), jnp.int32
    )
    first = None
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first / 2, (first, float(loss))


def test_moe_forward_aux_positive():
    from horovod_tpu.parallel.transformer import forward_with_aux

    cfg = _cfg(moe_experts=4)
    mesh = _mesh222()
    params = init_params(cfg, jax.random.PRNGKey(2))
    tokens = jnp.zeros((4, 32), jnp.int32)
    logits, aux = jax.shard_map(
        lambda p, t: forward_with_aux(p, t, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg), P("dp", "sp")),
        out_specs=(P("dp", "sp"), P()),
        check_vma=False,
    )(params, tokens)
    assert logits.shape == (4, 32, cfg.vocab_size)
    assert float(aux) > 0  # Switch balance loss is >= 1 per MoE layer


def test_train_step_with_equal_dmodel_dff():
    # Review regression: opt-state specs keyed by path, not shape
    # (d_model == d_ff used to collide).
    cfg = _cfg(d_model=64, d_ff=64, n_heads=4)
    mesh = _mesh222()
    opt = optax.adam(1e-2)
    params, opt_state = shard_init(cfg, mesh, jax.random.PRNGKey(0), opt)
    step = make_parallel_train_step(cfg, opt, mesh)
    tokens = jnp.zeros((4, 32), jnp.int32)
    params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
