"""Kimi Delta Attention layers beside a NoPE latent-attention layer, routed
experts held by share (``models/linear_moe.py``, ``ops/kda_kernels.py``,
``latent_moe.LatentAttention``'s two settings): the system against the
benchmark's plain reference (``benchmark/lib/plain_linear_moe.py``, which
runs the recurrence a position at a time and shares no code with it) at
tiny sizes, seeded weights, float32. ``A_log``, ``dt_bias`` and the
convolutions' taps are drawn as the configuration's ``assumed`` says, so
the decay is not degenerate.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import plain_linear_moe as plain
from horovod_tpu.models import latent_moe
from horovod_tpu.models.latent_moe import (
    LatentAttention, LatentMoEConfig, RoutedExperts,
)
from horovod_tpu.models.linear_moe import (
    KimiDeltaAttention, LinearMoEConfig, LinearMoELM, conv_silu, lm_loss,
)
from horovod_tpu.models.transformer import (
    RMSNorm, dot_product_attention, rotary, rotary_tables,
)

SEQ = 32


def _tiny(**kw):
    kw.setdefault("use_flash", False)
    kw.setdefault("use_kernel", False)
    return LinearMoEConfig.tiny(dtype=jnp.float32, **kw)


def _sizes(cfg: LinearMoEConfig, **kw) -> plain.Sizes:
    return plain.Sizes(
        n_layers=cfg.n_layers, n_dense_layers=cfg.n_dense_layers,
        kda_layers=cfg.kda_layers, kda_heads=cfg.kda_heads,
        kda_head_dim=cfg.kda_head_dim, n_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_dim=cfg.v_dim,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        routed_scale=cfg.routed_scale, eps=cfg.eps, scan_group=8, q_block=8,
        **kw,
    )


def _system(cfg):
    model = LinearMoELM(cfg)

    def logits(params, tokens):
        return model.apply({"params": params}, tokens[:, :-1])

    def loss(params, tokens):
        return lm_loss(logits(params, tokens), None, tokens, mtp_weight=0.0)

    return logits, loss


def _tokens(cfg, seed, batch=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, SEQ + 1), 0, cfg.vocab_size
    )


def _params(cfg, seed=0, scale=5.0):
    """Seeded weights, the matrices scaled up so that the mixers, the
    gates and the routing all move the loss by more than rounding; the
    decay's parameters and the taps stay as drawn."""
    init = LinearMoELM(
        dataclasses.replace(cfg, use_flash=False, use_kernel=False)
    )
    params = init.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or "conv" in str(path[-1])
        else x * scale, params,
    )


def _assert_trees_close(got, want, tol):
    got, want = (
        dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (got, want)
    )
    assert got.keys() == want.keys()
    for path, w in want.items():
        scale = float(jnp.abs(w).max())
        assert scale > 0, f"reference gradient of {path} is all zero"
        np.testing.assert_allclose(
            got[path], w, atol=tol * scale, rtol=0,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_loss_logits_and_every_gradient_leaf_match_the_plain_reference(
    kernels
):
    """Through the recurrence and XLA attention, and through both kernel
    families (interpreted)."""
    cfg = _tiny(use_flash=kernels, use_kernel=kernels)
    params, tokens = _params(cfg), _tokens(cfg, 1)
    logits, loss = _system(cfg)
    z = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(loss))(params, tokens)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: plain.loss(p, tokens, z)
        ))(params)
        system_logits = jax.jit(logits)(params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert system_logits.dtype == jnp.float32
    # the loss of these logits is the reference's, label by label
    np.testing.assert_allclose(
        plain.cross_entropy(system_logits, tokens[:, 1:]), want, rtol=2e-6
    )
    _assert_trees_close(got_grads, want_grads, 1e-4)
    decay = got_grads["block_1"]["attn"]
    for leaf in ("A_log", "dt_bias", "conv_q", "conv_k", "conv_v", "b",
                 "f_b", "g_b", "o_norm"):
        assert float(jnp.abs(decay[leaf]).max()) > 0, leaf


@pytest.mark.parametrize(
    "departure", ["no_decay", "beta_one", "no_conv"]
)
def test_each_departure_of_the_reference_moves_the_loss(departure):
    """The controls the benchmark runs at the published widths
    (``families/linear_moe_lm.controls``) are real departures: at these
    weights each moves the loss by more than the sound pair differs."""
    cfg = _tiny()
    params, tokens = _params(cfg), _tokens(cfg, 2)
    with jax.default_matmul_precision("highest"):
        sound, wrong = jax.jit(lambda p: (
            plain.loss(p, tokens, _sizes(cfg)),
            plain.loss(p, tokens, _sizes(cfg, departure=departure)),
        ))(params)
    assert abs(float(wrong - sound)) > 1e-4 * float(sound)


def test_layout_is_data():
    """Entries 1-5 of the published lists: KDA, KDA, KDA, latent, KDA;
    layer 1 has the dense FFN, every other layer routed experts."""
    published = LinearMoEConfig()
    assert [published.mixer(i) for i in range(5)] == [
        "kda", "kda", "kda", "latent", "kda"
    ]
    assert sum(published.mixer(i) == "kda" for i in range(27)) == 20
    assert published.mixer(26) == "latent"
    cfg = _tiny()
    params = _params(cfg)
    for i in range(cfg.n_layers):
        attn, ffn = params[f"block_{i}"]["attn"], params[f"block_{i}"]["ffn"]
        assert ("A_log" in attn) == (i != 3) and ("kv_a" in attn) == (i == 3)
        assert ("router" in ffn) == (i != 0)
    # NoPE, no q rank: one q projection and no q norm
    assert set(params["block_3"]["attn"]) == {"q", "kv_a", "kv_norm", "kv_b",
                                              "o"}
    # another layout, the same code: a latent layer first
    other = _tiny(kda_layers=(2,), full_attn_layers=(1,), n_layers=2)
    tree = _params(other)
    assert "kv_a" in tree["block_0"]["attn"] and "A_log" in tree["block_1"]["attn"]
    z = _sizes(other)
    tokens = _tokens(other, 3)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            _system(other)[1](tree, tokens), plain.loss(tree, tokens, z),
            rtol=2e-6,
        )


def test_shares_add_up_to_the_uncut_layer():
    """A 32-expert layer cut four ways, 8 experts a share: the four
    shares' routed parts, with the shared expert counted once, sum to what
    the plain reference gives for the whole layer (all 32 experts held)."""
    cfg = _tiny()
    whole = dataclasses.replace(cfg, n_experts_held=32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model))
    params = RoutedExperts(whole).init(jax.random.PRNGKey(4), x)["params"]
    params = jax.tree.map(lambda p: p * 8.0, params)
    want = plain.routed_experts(params, x, _sizes(whole))
    shared_only = plain.gated_mlp(
        x, *(params["shared"][n]["kernel"] for n in ("gate", "up", "down"))
    )
    total = shared_only
    for chip in range(4):
        share_cfg = dataclasses.replace(cfg, first_expert=8 * chip)
        share = {
            k: v[8 * chip:8 * chip + 8] if k.startswith("experts_") else v
            for k, v in params.items()
        }
        out = RoutedExperts(share_cfg).apply({"params": share}, x)
        # the chip's routed part: its output less the shared expert's
        total = total + (out - shared_only)
    assert float(jnp.abs(want - shared_only).max()) > 1e-2
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_convolution_is_causal_and_four_taps_deep():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 8))
    taps = jax.random.uniform(jax.random.PRNGKey(1), (4, 8), minval=-0.5,
                              maxval=0.5)
    y = conv_silu(x, taps)
    for t in (0, 2, 7):
        want = sum(
            taps[i] * x[0, t - 3 + i] for i in range(4) if t - 3 + i >= 0
        )
        np.testing.assert_allclose(y[0, t], jax.nn.silu(want), atol=1e-6)
    # position t sees nothing after itself and nothing before t - 3
    moved = conv_silu(x.at[0, 8].add(1.0), taps)
    changed = np.abs(np.asarray(moved - y)[0]).max(axis=-1) > 0
    assert changed.tolist() == [8 <= t <= 11 for t in range(12)]


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_the_convolutions_run_where_the_mixer_runs(kernels):
    """On the kernel path the mixer hands its taps to ``kda_attention``
    (``conv=``): the four KDA layers build eight kernels that convolve and
    the forward traces no operation under ``kda_conv`` (what is left of
    that scope is the backward's sum of the taps' partial gradients). On
    the recurrence path ``conv_silu`` runs under ``kda_conv`` in front of
    it and no kernel is built."""
    import model_parts
    from horovod_tpu.obs import registry

    cfg = _tiny(use_flash=False, use_kernel=kernels)
    params, tokens = _params(cfg), _tokens(cfg, 3)
    logits, loss = _system(cfg)
    counter = registry.always().counter("kda.calls.conv")
    before = counter.get()
    forward = jax.make_jaxpr(logits)(params, tokens)
    backward = jax.make_jaxpr(jax.grad(loss))(params, tokens)
    n_kda = len(cfg.kda_layers)
    assert counter.get() - before == (3 * n_kda if kernels else 0)

    def under_conv(jaxpr):
        return sum(
            "kda_conv" in stack.split("/") and computed
            for _, stack, computed in model_parts.operations(jaxpr.jaxpr)
        )

    assert (under_conv(forward) == 0) == kernels
    assert under_conv(backward) > under_conv(forward)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_the_head_wise_norm_runs_where_the_mixer_runs(kernels):
    """On the kernel path the mixer asks ``kda_attention`` for the norm
    (``out_norm=``): the four KDA layers build kernels that normalise their
    exit, and under ``kda_gate`` the forward traces neither the mean of
    squares nor its ``rsqrt`` nor a 4-d array of heads (what is left there
    of the gated norm is its scale and gate, elementwise on ``[B, S, H
    d]``). On the recurrence path the gated norm is XLA's, written out on
    heads, and no kernel is built."""
    import model_parts
    from horovod_tpu.obs import registry

    cfg = _tiny(use_flash=False, use_kernel=kernels)
    params, tokens = _params(cfg), _tokens(cfg, 3)
    logits, loss = _system(cfg)
    counter = registry.always().counter("kda.calls.out_norm")
    before = counter.get()
    forward = jax.make_jaxpr(logits)(params, tokens)
    jax.make_jaxpr(jax.grad(loss))(params, tokens)
    assert counter.get() - before == (
        3 * len(cfg.kda_layers) if kernels else 0
    )
    gate = {
        primitive
        for primitive, stack, _ in model_parts.operations(forward.jaxpr)
        if "kda_gate" in stack.split("/")
    }
    assert "logistic" in gate and "mul" in gate
    statistic = {"rsqrt", "reduce_sum"} & gate
    assert statistic == (set() if kernels else {"rsqrt", "reduce_sum"})


def test_the_kernel_path_is_the_recurrence_path():
    """The same weights and tokens through both paths of the model: the
    kernels convolve at their door and normalise at their exit, the
    recurrence path does both in XLA; loss and every gradient leaf agree
    at the tolerance the plain reference is held to."""
    paths = [_tiny(use_flash=False, use_kernel=k) for k in (True, False)]
    params, tokens = _params(paths[0]), _tokens(paths[0], 4)
    with jax.default_matmul_precision("highest"):
        (got, got_grads), (want, want_grads) = (
            jax.jit(jax.value_and_grad(_system(cfg)[1]))(params, tokens)
            for cfg in paths
        )
    np.testing.assert_allclose(got, want, rtol=2e-6)
    _assert_trees_close(got_grads, want_grads, 1e-4)
    assert float(jnp.abs(got_grads["block_0"]["attn"]["o_norm"]).max()) > 0


def test_mixer_keeps_the_decay_and_the_state_in_float32():
    """``g``, ``beta`` reach the kernels' entry as float32 whatever the
    compute dtype, and ``g <= 0``."""
    cfg = LinearMoEConfig.tiny(use_kernel=False)  # bfloat16 compute
    seen = {}
    from horovod_tpu.models import linear_moe

    real = linear_moe.kda_attention

    def record(q, k, v, g, beta, **kw):
        seen.update(q=q, g=g, beta=beta)
        return real(q, k, v, g, beta, **kw)

    u = jax.random.normal(jax.random.PRNGKey(0), (1, 16, cfg.d_model),
                          jnp.bfloat16)
    mixer = KimiDeltaAttention(cfg)
    params = mixer.init(jax.random.PRNGKey(1), u)["params"]
    linear_moe.kda_attention = record
    try:
        out = mixer.apply({"params": params}, u)
    finally:
        linear_moe.kda_attention = real
    assert out.dtype == jnp.bfloat16 and seen["q"].dtype == jnp.bfloat16
    assert seen["g"].dtype == seen["beta"].dtype == jnp.float32
    assert float(seen["g"].max()) <= 0.0 and float(seen["g"].min()) < -1e-3
    rate = np.exp(np.asarray(params["A_log"]))
    assert (rate >= 1.0).all() and (rate <= 16.0).all()
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001


class _LatentAttentionAsItStood(nn.Module):
    """``latent_moe.LatentAttention`` as the parent commit had it."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, n, r, v = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=cfg.dtype, name=name,
            kernel_init=nn.initializers.normal(cfg.init_std),
        )
        norm = lambda name: RMSNorm(cfg.eps, cfg.dtype, name=name)  # noqa: E731
        with jax.named_scope("mla_proj"):
            q = dense(h * (n + r), "q_b")(
                norm("q_norm")(dense(cfg.q_lora_rank, "q_a")(x))
            )
            kv_a = dense(cfg.kv_lora_rank + r, "kv_a")(x)
            kv = dense(h * (n + v), "kv_b")(
                norm("kv_norm")(kv_a[..., :cfg.kv_lora_rank])
            )
            k_rope = rotary(
                kv_a[..., cfg.kv_lora_rank:], theta=cfg.rope_theta
            )
        if cfg.use_flash:
            from horovod_tpu.ops.pallas_kernels import (
                QRotary, flash_attention_latent,
            )

            out, _ = flash_attention_latent(
                q, kv, k_rope, causal=True, n_heads=h, q_rotary=QRotary(
                    *rotary_tables(s, r, theta=cfg.rope_theta), start=n
                ),
            )
        else:
            with jax.named_scope("mla_proj"):
                q = q.reshape(b, s, h, n + r)
                q = jnp.concatenate([
                    q[..., :n], rotary(q[..., n:], theta=cfg.rope_theta)
                ], axis=-1)
                kv = kv.reshape(b, s, h, n + v)
                k = jnp.concatenate([
                    kv[..., :n],
                    jnp.broadcast_to(k_rope[:, :, None], (b, s, h, r)),
                ], axis=-1)
            with jax.named_scope("attn_xla"):
                out = dot_product_attention(q, k, kv[..., n:], causal=True)
            with jax.named_scope("attn_layout"):
                out = out.reshape(b, s, h * v)
        with jax.named_scope("mla_proj"):
            return dense(cfg.d_model, "o")(out)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_latent_attention_defaults_trace_what_they_traced(flash):
    """``q_lora_rank`` set and ``use_rope=True`` are the defaults: the
    module traces, forward and backward, the jaxpr the parent's traced."""
    cfg = LatentMoEConfig.tiny(use_flash=flash)
    assert cfg.use_rope and cfg.q_lora_rank == 32
    x = jax.ShapeDtypeStruct((2, 24, cfg.d_model), jnp.bfloat16)
    traced = []
    for module in (LatentAttention(cfg), _LatentAttentionAsItStood(cfg)):
        params = jax.eval_shape(
            lambda m=module: m.init(
                jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)
            )["params"]
        )
        traced.append(str(jax.make_jaxpr(jax.grad(
            lambda p, x, m=module: m.apply({"params": p}, x).astype(
                jnp.float32).sum()
        ))(params, x)))
        jax.clear_caches()  # the flash entries keep their traces
    assert traced[0] == traced[1]


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_nope_latent_attention_without_a_q_rank_is_its_equations(flash):
    """``q = u W_q``; ``[c_kv | k_r] = u W_kva``; ``[k_nope | v] =
    RMSNorm(c_kv) W_kvb``; ``k = [k_nope | k_r]``, nothing rotated;
    ``softmax_{j<=i}(q_i . k_j / sqrt(n + r)) v_j``; ``W_o``: written out
    here, and equal to the plain reference's."""
    cfg = _tiny(use_flash=flash)
    h, n, r, v = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    u = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, cfg.d_model))
    module = LatentAttention(cfg)
    params = module.init(jax.random.PRNGKey(1), u)["params"]
    params = jax.tree.map(lambda p: p * 6.0 if p.ndim > 1 else p, params)
    assert set(params) == {"q", "kv_a", "kv_norm", "kv_b", "o"}
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": params}, u)
        b, s, _ = u.shape
        q = (u @ params["q"]["kernel"]).reshape(b, s, h, n + r)
        kv_a = u @ params["kv_a"]["kernel"]
        c_kv, k_r = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
        c_kv = c_kv * jax.lax.rsqrt(
            jnp.mean(c_kv * c_kv, -1, keepdims=True) + cfg.eps
        ) * params["kv_norm"]["scale"]
        kv = (c_kv @ params["kv_b"]["kernel"]).reshape(b, s, h, n + v)
        k = jnp.concatenate([
            kv[..., :n], jnp.broadcast_to(k_r[:, :, None], (b, s, h, r))
        ], axis=-1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(n + r)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
        want = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), kv[..., n:]
        ).reshape(b, s, h * v) @ params["o"]["kernel"]
        reference = plain.latent_attention(params, u, _sizes(cfg))
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(
        reference, want, atol=2e-5 * float(jnp.abs(want).max())
    )
    # position matters through the mask only: no rotation anywhere
    assert latent_moe.LatentMoEConfig().use_rope and not cfg.use_rope
