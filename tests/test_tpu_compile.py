"""Every public Pallas kernel compiles for the TPU v5e at model widths.

No chip is needed: libtpu compiles for a *described* ``v5e:2x2`` topology
(``jax.experimental.topologies``), with ``interpret=False`` and shapes in
place of arrays, and raises what the chip's compiler would raise. Nothing
runs, so these say nothing about results or times — they catch what the
interpreter cannot (a primitive Mosaic will not legalize, a misaligned
slice, too much VMEM) before a chip call is spent on it.

One process: the TPU compiler's lock file allows one loader at a time, so
no fast-tier test may load it from a child process. (Whole cells compiled
at their real size, minutes each, are in ``test_compile_plan.py``.)
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import tools.step_hash as step_hash
from horovod_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def v5e(v5e_topology):
    """Sharding on one described v5e chip."""
    return SingleDeviceSharding(v5e_topology.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile_layer_grad(topo, sharding, module, x, loss):
    """HLO text of ``value_and_grad(loss)(params, x)`` of one flax layer on
    shapes, compiled for one described chip as the world's device (so the
    layer takes its TPU path: the kernels)."""
    import horovod_tpu as hvd

    hvd.init(devices=topo.devices[:1])
    try:
        params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            (params, x),
        )
        return params, jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1))
        ).lower(*args).compile().as_text()
    finally:
        hvd.shutdown()


def _assert_dq_result_is_q_cotangent(hlo):
    """The program's first result is the dQ kernel's first result as it
    leaves the kernel (its second is the row statistic)."""
    entry = hlo[hlo.index("\nENTRY"):]
    first = re.search(r"tuple\(%([\w.]+)", entry.split("ROOT ")[1])[1]
    (made,) = [line for line in entry.splitlines()
               if line.lstrip().startswith(f"%{first} = ")]
    assert re.search(
        r"get-tuple-element\(%[\w.]*hvd_flash_bwd_dq[\w.]*\), index=0", made
    ), made[:300]


# -- the models' parts change the compiled step in its metadata only --------
# (first in the file: these compiles use every core, and the file's last
# tests run beside test_trace.py's deadline-bound scenarios as it is)


def _described_family_step(topo, case):
    """HLO text of a tiny family's default step (``tests/model_parts.py``)
    compiled for one described chip, with and without its metadata."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    import model_parts
    from horovod_tpu.parallel import dp

    hvd.init(devices=topo.devices[:1])
    try:
        _, _, loss_fn, params, batch = model_parts.build(case, 2)
        step, wrapped = dp.make_train_step(loss_fn, optax.adamw(3e-4))
        state = jax.eval_shape(lambda p: dp.init_state(p, wrapped), params)
        placed = lambda tree, spec: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(hvd.mesh(), spec)
            ), tree,
        )
        hlo = step.lower(
            placed(state, P()), placed(batch, P(hvd.WORLD_AXIS))
        ).compile().as_text()
    finally:
        hvd.shutdown()
    return step_hash.bare_hlo(hlo), hlo


@pytest.mark.parametrize(
    "case", ["gpt2-flash", "bert-cls-padded", "latent-moe-flash",
             "window-moe-flash", "linear-dense-kernels"]
)
def test_parts_change_the_compiled_step_in_its_metadata_only(
    v5e_topology, case
):
    """The step of each family compiled for the described v5e, against
    the same model with no part opened (a patch of ``jax.named_scope``,
    local to this test): the same instructions under the same names; the
    parts are in the scoped build's ``op_name``s and in none of the
    bare one's."""
    import model_parts

    # a Mosaic kernel's payload carries the call sites of whoever traced
    # it first in this process: both builds trace it anew
    jax.clear_caches()
    scoped, scoped_full = _described_family_step(v5e_topology, case)
    with model_parts.parts_disabled():
        bare, bare_full = _described_family_step(v5e_topology, case)
    assert scoped == bare
    kernels = {"bert-cls-padded": 0, "latent-moe-flash": 9,
               "window-moe-flash": 12, "linear-dense-kernels": 9}.get(case, 6)
    assert scoped.count("tpu_custom_call") == kernels
    part = re.compile(r'op_name="[^"]*/(?:%s)/' % "|".join(model_parts.PARTS))
    assert part.search(scoped_full) and not part.search(bare_full)


def test_step_hash_repeats_and_is_blind_to_metadata_only(
    v5e_topology, capsys
):
    """``tools/step_hash.py`` on a cell at its files' ``tiny`` sizes, for
    the described v5e: twice it prints the same line; with no part opened
    in the model, the same program (``stablehlo``, ``hlo_bare``, the
    count of kernels) under other names (``hlo``, ``op_names``). The MLM
    cell: GPT-2's 32 tiny positions are fewer than Mosaic takes a causal
    kernel for."""
    import json

    import model_parts

    argv = ["--described", "--tiny", "--workload", "bert-base.mlm-b32-s512"]

    def line():
        assert step_hash.main(argv) == 0
        return json.loads(capsys.readouterr().out)

    first, again = line(), line()
    with model_parts.parts_disabled():
        unnamed = line()
    assert first == again
    assert first["custom_calls"] == 6
    same = ("stablehlo", "hlo_bare", "custom_calls")
    assert [first[k] for k in same] == [unnamed[k] for k in same]
    assert first["op_names"] != unnamed["op_names"]
    assert first["hlo"] != unnamed["hlo"]


# -- the seam between a large weight's gradient and its update (PR 52) -------
# (early in the file for the same reason; ``_described_lm_step`` is below)


def _update_fused_into_a_matmul(hlo):
    """Root shapes of the fused computations that hold both a
    ``convolution`` (the TPU compiler's matmul) and an instruction of the
    ``hvd_update`` scope: a weight's update made the epilogue of its
    dW."""
    roots = []
    for body in re.findall(r"(?m)^%fused_computation[^\n]*\{\n(.*?)^\}", hlo,
                           re.S):
        if " convolution(" in body and "hvd_update" in body:
            roots.append(re.search(r"ROOT [^=]*= (.*?) \w[\w-]*\(", body)[1])
    return roots


def test_update_seams_keep_large_updates_out_of_their_dw(
    v5e_topology, monkeypatch
):
    """With the constant patched down to the small model's largest
    leaves (the tied 2048 x 512 table, the FFN's 512 x 2048 and 2048 x
    512), the compiled one-chip step fuses none of their updates into the
    matmul that computes the gradient; with the constant where it is, the
    compiler does exactly that (what the seam is for). Smaller leaves keep
    the fused form either way, and the program lowered for four devices,
    where the exchange stands, does not change by a character."""
    import horovod_tpu as hvd
    from horovod_tpu.obs import registry
    from horovod_tpu.ops import fusion

    large = re.compile(r"f32\[(2048,512|512,2048)\]")

    def lowered(n_devices):
        try:
            step, state, batch = _described_lm_step(v5e_topology, n_devices)
            return step.lower(state, batch)
        finally:
            hvd.shutdown()

    fused_plain = _update_fused_into_a_matmul(
        lowered(1).compile().as_text()
    )
    four_plain = lowered(4).as_text()
    monkeypatch.setattr(fusion, "UPDATE_SEAM_MIN_SIZE", 2 ** 20)
    fused_seamed = _update_fused_into_a_matmul(
        lowered(1).compile().as_text()
    )
    # the table, and gate and down of four blocks
    assert registry.always().gauge("fusion.update_seams").get() == 9
    assert any(large.search(root) for root in fused_plain), fused_plain
    assert not any(large.search(root) for root in fused_seamed), fused_seamed
    assert fused_seamed, "small leaves keep their update in the dW fusion"
    # what the compiler is handed for four devices is the same text
    assert lowered(4).as_text() == four_plain
    assert registry.always().gauge("fusion.update_seams").get() == 0


@pytest.mark.parametrize(
    "batch,seq,causal,heads",
    [(16, 1024, True, 12), (16, 1024, True, 6), (16, 1024, True, 16),
     (4, 2048, True, 12), (16, 640, True, 18), (32, 512, False, 12)],
    ids=["gpt2-16x1024-causal", "6-heads-causal", "16-heads-causal",
         "s2048-causal", "18-heads-s640-causal", "bert-32x512"],
)
def test_flash_attention_fwd_bwd_compiles(v5e, batch, seq, causal, heads):
    """Packed ``bsm`` layout with heads of 64, as ``models/transformer.py``
    calls it (no block sizes given); forward and both backward kernels.
    The causal cases cross what the plan decides from the shape: the whole
    K/V of 1024 resident (12 heads: groups of 4), head counts whose
    budget-sized group would be 3 heads, 192 lanes, which Mosaic refuses
    (6, 18), a padded length, and two K/V blocks (s 2048: the forward's
    unmasked slab)."""

    def loss(q, k, v):
        out = pk.flash_attention(
            q, k, v, causal=causal, layout="bsm", n_heads=heads,
            interpret=False,
        )
        return out.astype(jnp.float32).sum()

    qkv = ((batch, seq, 64 * heads), jnp.bfloat16)
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, qkv, qkv, qkv)
    assert hlo.count("tpu_custom_call") >= 3  # fwd + dkdv + dq


@pytest.mark.parametrize(
    "batch,seq,heads,qk,v",
    [(2, 4096, 32, 192, 128), (4, 1024, 8, 192, 128), (2, 1024, 16, 128, 64)],
    ids=["latent-2x4096x32", "latent-s1024x8", "qk128-v64"],
)
def test_flash_attention_split_widths_compile(v5e, batch, seq, heads, qk, v):
    """q / k heads wider than v / out heads, packed: the latent
    attention's 192 / 128 at its cell's shape (32 heads, s 4096: groups of
    2 heads, two K/V blocks of 1024 resident in turn) and at a shorter
    one, and a v narrower than the lanes under a q / k at them (dV's
    accumulator streams its thin operand, dK's does not)."""

    def loss(q, k, value):
        out = pk.flash_attention(
            q, k, value, causal=True, layout="bsm", n_heads=heads,
            interpret=False,
        )
        return out.astype(jnp.float32).sum()

    wide = ((batch, seq, qk * heads), jnp.bfloat16)
    narrow = ((batch, seq, v * heads), jnp.bfloat16)
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, wide, wide, narrow)
    assert hlo.count("tpu_custom_call") >= 3


@pytest.mark.parametrize(
    "batch,seq,heads", [(2, 4096, 32), (4, 1000, 8)],
    ids=["latent-2x4096x32", "latent-s1000x8-padded"],
)
def test_flash_attention_latent_compiles(v5e, batch, seq, heads):
    """The packed ``kv`` and the shared rotary key through the three
    kernels at 128 + 64 / 128: the expert cell's shape (groups of 2 heads,
    a ``[1, 1024, 512]`` block of ``kv`` beside a ``[1, 1024, 64]`` block
    of the key, dK/dV's float32 partial of the key's gradient) and a
    padded length."""

    def loss(q, kv, k_rope):
        out, lse = pk.flash_attention_latent(
            q, kv, k_rope, causal=True, n_heads=heads, interpret=False
        )
        return out.astype(jnp.float32).sum() + (lse ** 2).sum()

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), v5e,
        ((batch, seq, 192 * heads), jnp.bfloat16),
        ((batch, seq, 256 * heads), jnp.bfloat16),
        ((batch, seq, 64), jnp.bfloat16),
    )
    assert hlo.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("norm", [False, True], ids=["", "out-norm"])
@pytest.mark.parametrize("conv", [False, True], ids=["plain", "conv"])
@pytest.mark.parametrize(
    "batch,seq,heads", [(1, 8192, 32), (2, 1000, 4)],
    ids=["kda-1x8192x32", "kda-s1000x4-padded"],
)
def test_kda_attention_fwd_bwd_compiles(v5e, batch, seq, heads, conv, norm):
    """The Kimi Delta Attention kernels, forward and backward, at the
    cell's shape (1 x 8,192, 32 heads of 128 key and value channels,
    bfloat16 operands, float32 ``g`` and ``beta``) and at a padded length:
    two Mosaic calls, the chunks' entry states the only array between
    them that the entry did not take or hand back. With ``conv`` (the
    cell's call since PR 43: four taps an operand, convolved and gated in
    VMEM) the same two calls, the taps' gradients leaving the backward as
    one float32 ``[8, 128]`` block a head and batch row. With ``out_norm``
    (the cell's call since PR 45: a head's output normalised at the exit)
    the same two calls again, ``1 / rms`` leaving the forward as a float32
    row vector a head, as ``dbeta`` leaves the backward."""
    from horovod_tpu.ops.kda_kernels import KdaConv, kda_attention

    def loss(q, k, v, g, beta, *taps):
        return kda_attention(
            q, k, v, g, beta, n_heads=heads, use_kernel=True,
            conv=KdaConv(*taps) if conv else None,
            out_norm=1e-5 if norm else None, interpret=False,
        ).astype(jnp.float32).sum()

    wide = ((batch, seq, 128 * heads), jnp.bfloat16)
    taps = [((4, 128 * heads), jnp.float32)] * (3 if conv else 0)
    hlo = _compile(
        jax.grad(loss, argnums=tuple(range(5 + len(taps)))), v5e, wide, wide,
        wide, ((batch, seq, 128 * heads), jnp.float32),
        ((batch, seq, heads), jnp.float32), *taps,
    )
    assert hlo.count("tpu_custom_call") == 2
    assert "hvd_kda_fwd" in hlo and "hvd_kda_bwd" in hlo
    padded = -(-seq // 128) * 128
    assert f"bf16[{batch},{heads},{padded // 64},128,128]" in hlo
    assert (f"f32[{batch},{heads},8,128]" in hlo) == conv
    (fwd,) = [line for line in hlo.splitlines()
              if "tpu_custom_call" in line and "hvd_kda_fwd" in line]
    assert (f"f32[{batch},{heads},1,{padded}]" in fwd.split(" custom-call(")[0]
            ) == norm


@pytest.mark.parametrize(
    "seq,door", [(1000, False), (2048, True)],
    ids=["gdn-s1000-padded", "gdn-s2048-conv-norm"],
)
def test_gdn_attention_fwd_bwd_compiles(v5e, seq, door):
    """The scalar-gate kernels, forward and backward, at the Olmo-Hybrid
    cell's head widths (96 key and 192 value channels, no whole 128-lane
    tiles: five heads put a head's first lane at every residue 96 steps
    reach, 0 / 96 / 64 / 32) on bfloat16 operands that HBM holds at their
    own widths, with and without the door and the exit: two Mosaic calls,
    the chunks' entry states ``[B, chunks, H, 192, 96]`` the only array
    between them that the entry did not take or hand back; ``dg``,
    ``dbeta`` and ``1 / rms`` leave as ``[B, S, H]``, no row vectors."""
    from horovod_tpu.ops.kda_kernels import KdaConv, kda_attention

    heads, dk, dv = 5, 96, 192

    def loss(q, k, v, g, beta, *taps):
        return kda_attention(
            q, k, v, g, beta, n_heads=heads, use_kernel=True,
            conv=KdaConv(*taps) if door else None,
            out_norm=1e-6 if door else None, interpret=False,
        ).astype(jnp.float32).sum()

    keys = ((1, seq, dk * heads), jnp.bfloat16)
    values = ((1, seq, dv * heads), jnp.bfloat16)
    gate = ((1, seq, heads), jnp.float32)
    taps = [((4, d * heads), jnp.float32) for d in (dk, dk, dv)] * door
    hlo = _compile(
        jax.grad(loss, argnums=tuple(range(5 + len(taps)))), v5e, keys, keys,
        values, gate, gate, *taps,
    )
    assert hlo.count("tpu_custom_call") == 2
    assert "hvd_gdn_fwd" in hlo and "hvd_gdn_bwd" in hlo
    assert "hvd_kda_" not in hlo
    padded = -(-seq // 128) * 128
    assert f"bf16[1,{padded // 64},{heads},{dv},{dk}]" in hlo
    assert (f"f32[1,8,{dv * heads}]" in hlo) == door  # the taps' partials
    (fwd,) = [line for line in hlo.splitlines()
              if "tpu_custom_call" in line and "hvd_gdn_fwd" in line]
    assert (f"f32[1,{padded},{heads}]" in fwd.split(" custom-call(")[0]
            ) == door


def test_kda_mixer_turns_no_float32_heads_around_its_norm(v5e_topology, v5e):
    """One ``KimiDeltaAttention``'s ``value_and_grad`` at the cell's shape
    (1 x 8,192 x 2,304, 32 heads of 128), compiled: the kernels normalise a
    head's output at their exit, so the program reshapes nothing to heads
    and the compiler sets no ``copy`` of a float32 ``[.., 32, 128]`` array
    around the norm (until PR 45 three a layer, 268 MB of traffic each,
    with three fusions that existed only to make the float32 array the
    copy turned), and no float32 array of that shape exists at all."""
    from horovod_tpu.models.linear_moe import (
        KimiDeltaAttention, LinearMoEConfig,
    )

    mixer = KimiDeltaAttention(LinearMoEConfig())
    x = jax.ShapeDtypeStruct((1, 8192, 2304), jnp.bfloat16)

    def loss(params, x):
        return mixer.apply(params, x).astype(jnp.float32).sum()

    params, hlo = _compile_layer_grad(v5e_topology, v5e, mixer, x, loss)
    assert hlo.count("tpu_custom_call") == 2
    assert not re.findall(r"= f32\[[\d,]*32,128\]\S* copy\(", hlo)
    assert not re.findall(r"f32\[[\d,]*,32,128\]", hlo)


@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_flash_attention_window_and_groups_compile(v5e, window):
    """The window cell's attention, 1 x 16,384 with 28 query heads on 4
    K/V heads of 128, packed: the three kernels compile with 7 query heads
    and ONE K/V head a program, under the band (whose grids are 6 and 11
    steps long where the causal call's are 16 and 32) and without it; K, V,
    dK and dV are ``[1, 16384, 512]`` wherever a kernel touches them."""
    def loss(q, k, v):
        out, lse = pk.flash_attention_with_lse(
            q, k, v, causal=True, window=window, layout="bsm", n_heads=28,
            n_kv_heads=4, interpret=False,
        )
        return out.astype(jnp.float32).sum() + (lse ** 2).sum()

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), v5e,
        ((1, 16384, 28 * 128), jnp.bfloat16),
        ((1, 16384, 4 * 128), jnp.bfloat16),
        ((1, 16384, 4 * 128), jnp.bfloat16),
    )
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    suffix = "_window" if window else ""
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"):
        (call,) = [c for c in calls if f"{name}{suffix}" in c.split(" = ")[0]
                   or f'/{name}{suffix}"' in c]
        wide = len(re.findall(r"bf16\[1,16384,3584\]", call))
        narrow = len(re.findall(r"bf16\[1,16384,512\]", call))
        # fwd: q, out / k, v; dkv: q, g / k, v, dk, dv; dq: q, g, out, dq /
        # k, v
        assert (wide, narrow) == {
            "hvd_flash_fwd": (2, 2), "hvd_flash_bwd_dkv": (2, 4),
            "hvd_flash_bwd_dq": (4, 2),
        }[name], (name, wide, narrow)
    p = pk._plan(
        *(jax.ShapeDtypeStruct((1, 16384, h * 128), jnp.bfloat16)
          for h in (28, 4, 4)),
        causal=True, block_q=512, block_k=512, interpret=False, n_heads=28,
        n_kv_heads=4, window=window or 0,
    )
    assert (p.group, p.kv_group, p.subs, p.block_k) == (7, 1, 1, 1024)
    assert (p.kv_steps, p.q_steps) == ((6, 11) if window else (16, 32))


def test_select_family_compiles_at_the_sparse_cells_shapes(v5e):
    """Learned sparse attention at 1 x 8,192, 32 query heads on 4 K/V heads
    of 128, an indexer of 16 x 64 on one key head: the select kernel (whose
    ``[8192, 512]`` block of ordered keys is 16 MB of VMEM), the three flash
    kernels under its int8 mask with q rotated at their door, and the
    index-loss kernel whose ``dk_idx`` stays in VMEM through the grid. Five
    Mosaic calls, each under its name."""
    from horovod_tpu.models.transformer import rotary_tables
    from horovod_tpu.ops import dsa_kernels as dsa

    s, h, h_kv, d, h_i, d_i = 8192, 32, 4, 128, 16, 64
    table = pk.QRotary(*rotary_tables(s, d, theta=1e7), halves=True)

    def loss(q, k, v, q_idx, k_idx, w):
        keep, _, lse_idx = dsa.dsa_select(
            q_idx, k_idx, w, top_k=2048, use_kernel=True, interpret=False,
            block_q=512, block_k=512,
        )
        out, lse = pk.flash_attention_with_lse(
            q, k, v, causal=True, layout="bsm", n_heads=h, n_kv_heads=h_kv,
            q_rotary=table, keep=keep, interpret=False,
        )
        return out.astype(jnp.float32).sum() + dsa.dsa_index_loss(
            q, k, lse, q_idx, k_idx, w, keep, lse_idx, n_heads=h,
            n_kv_heads=h_kv, use_kernel=True, interpret=False,
        )

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)), v5e,
        ((1, s, h * d), jnp.bfloat16), ((1, s, h_kv * d), jnp.bfloat16),
        ((1, s, h_kv * d), jnp.bfloat16), ((1, s, h_i * d_i), jnp.bfloat16),
        ((1, s, d_i), jnp.bfloat16), ((1, s, h_i), jnp.float32),
    )
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 5
    for name in ("hvd_dsa_select", "hvd_flash_fwd_select",
                 "hvd_flash_bwd_dkv_select", "hvd_flash_bwd_dq_select",
                 "hvd_dsa_kl"):
        (call,) = [c for c in calls if f'/{name}"' in c
                   or name in c.split(" = ")[0]]
        # each reads or writes the mask as it lies, keys by queries
        assert "s8[1,8192,8192]" in call, name
    p = pk._plan(
        *(jax.ShapeDtypeStruct((1, s, n * d), jnp.bfloat16)
          for n in (h, h_kv, h_kv)),
        causal=True, block_q=512, block_k=512, interpret=False, n_heads=h,
        n_kv_heads=h_kv, select=True,
    )
    assert (p.group, p.kv_group, p.subs, p.block_k) == (8, 1, 1, 1024)


@pytest.mark.parametrize(
    "cell", ["latent-pairs-2x4096x32", "window-halves-1x16384x28",
             "latent-pairs-s1000x8-padded"],
)
def test_flash_entries_with_rotary_tables_compile(v5e, cell):
    """``q_rotary`` at the two expert cells' widths: adjacent pairs on the
    last 64 of a head's 192 lanes (latent entry, groups of 2 heads) and
    halves on the whole of a head's 128 under the window with 7 query heads
    a program; and a padded length.  Forward, dK/dV and dQ stay three
    calls; the forward writes a second array shaped like q (the turned q,
    the backward's residual) and dQ writes the unrotated q's gradient in
    q's dtype: no float32 array of q's shape exists around the kernels."""
    from horovod_tpu.models.transformer import rotary_tables

    latent = cell.startswith("latent")
    padded = cell.endswith("padded")
    b, s, h = (4, 1000, 8) if padded else (2, 4096, 32) if latent else (
        1, 16384, 28
    )

    def loss(q, k, v):
        if latent:
            out, lse = pk.flash_attention_latent(
                q, k, v, causal=True, n_heads=h, interpret=False,
                q_rotary=pk.QRotary(
                    *rotary_tables(s, 64, theta=32e6), start=128
                ),
            )
        else:
            out, lse = pk.flash_attention_with_lse(
                q, k, v, causal=True, window=4096, layout="bsm", n_heads=h,
                n_kv_heads=4, interpret=False,
                q_rotary=pk.QRotary(
                    *rotary_tables(s, 128, theta=1.5e6), halves=True
                ),
            )
        return out.astype(jnp.float32).sum() + (lse ** 2).sum()

    widths = (192 * h, 256 * h, 64) if latent else (128 * h, 512, 512)
    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), v5e,
        *(((b, s, w), jnp.bfloat16) for w in widths),
    )
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    # (in the window call ``out`` has q's shape: the row statistic reads it
    # in dQ, as it lies)
    assert not re.search(rf"f32\[{b},{s},{widths[0]}\]", hlo)
    if not padded:
        # q's cotangent is the dQ kernel's first result itself
        _assert_dq_result_is_q_cotangent(hlo)
        q_shape = rf"bf16\[{b},{s},{widths[0]}\]"
        (fwd,) = [c for c in calls if "hvd_flash_fwd" in c]
        # q in; out is narrower in the latent call, so: q, the turned q
        # (and out, where a head's v is as wide as its q)
        assert len(re.findall(q_shape, fwd)) == (2 if latent else 3)


@pytest.mark.parametrize("cell", ["select-halves-1x8192x32",
                                  "select-halves-s1000x8-padded"])
def test_flash_entries_with_a_q_norm_compile(v5e, cell):
    """``q_norm`` at the sparse cell's widths (32 query heads on 4 K/V heads
    of 128, halves, under the int8 mask, 8 query heads a program) and at a
    padded length: forward, dK/dV and dQ stay three calls; the forward
    takes the projection's q and writes the q its scores see, dQ takes
    both and the ``[1, 128]`` scale and writes q's gradient in q's dtype
    and the scale's as float32 rows, one a program."""
    from horovod_tpu.models.transformer import rotary_tables

    padded = cell.endswith("padded")
    b, s, h, h_kv = (2, 1000, 8, 2) if padded else (1, 8192, 32, 4)

    def loss(q, k, v, keep, scale):
        out, lse, q_seen = pk.flash_attention_with_lse(
            q, k, v, causal=True, layout="bsm", n_heads=h, n_kv_heads=h_kv,
            keep=keep, interpret=False, return_q=True,
            q_rotary=pk.QRotary(
                *rotary_tables(s, 128, theta=1e7), halves=True
            ),
            q_norm=pk.QNorm(scale, 1e-6),
        )
        return (out.astype(jnp.float32).sum() + (lse ** 2).sum()
                + q_seen[:, 0].astype(jnp.float32).sum())

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 4)), v5e,
        ((b, s, h * 128), jnp.bfloat16), ((b, s, h_kv * 128), jnp.bfloat16),
        ((b, s, h_kv * 128), jnp.bfloat16), ((b, s, s), jnp.int8),
        ((128,), jnp.float32),
    )
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    s_pad = -(-s // 512) * 512
    q_shape = rf"bf16\[{b},{s_pad},{h * 128}\]"
    (fwd,) = [c for c in calls if "hvd_flash_fwd_select" in c]
    (dq,) = [c for c in calls if "hvd_flash_bwd_dq_select" in c]
    # forward: q in; out and the q the scores see out.  dQ: that q, the
    # raw q, g and out in; dq out
    assert len(re.findall(q_shape, fwd)) == 3
    assert len(re.findall(q_shape, dq)) == 5
    assert "f32[1,128]" in fwd and "f32[1,128]" in dq
    # a K/V head's query heads are one program's
    rows = rf"f32\[{b},{h_kv},{s_pad // 512},1,128\]"
    assert re.search(rows, dq.split(" custom-call(")[0])
    if not padded:  # q's cotangent is the dQ kernel's first result itself
        _assert_dq_result_is_q_cotangent(hlo)


def test_sparse_layer_norms_and_rotates_q_nowhere_but_in_the_kernels(
    v5e_topology, v5e
):
    """One ``GroupedAttention`` of the sparse cell (1 x 8,192 x 2,048, 32
    query heads on 4 K/V heads of 128, q / k norms, rotary, an indexer of
    16 x 64 keeping 2,048), ``value_and_grad`` compiled: the kernels norm
    and rotate q at their door and the index loss reads the forward's
    residual, so nothing of q's shape is left under ``attn_proj/q_norm``
    (until PR 48 five float32 copies of 134 MB a layer among six fusions
    that existed to feed them), XLA concatenates no rotated q, and no
    float32 ``[.., 8192, 4096]`` is copied at all."""
    from horovod_tpu.models.window_moe import (
        GroupedAttention, WindowMoEConfig,
    )

    attn = GroupedAttention(WindowMoEConfig(
        d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        window_layout=(0,), rope_layout=(1,), rope_theta=1e7, qk_norm=True,
        index_top_k=2048, index_heads=16, index_head_dim=64,
        index_blocks=(512, 512),
    ), rotate=True)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)

    def loss(params, x):
        out, index_loss = attn.apply(params, x)
        return out.astype(jnp.float32).sum() + index_loss

    params, hlo = _compile_layer_grad(v5e_topology, v5e, attn, x, loss)
    assert hlo.count("tpu_custom_call") == 5
    assert params["params"]["q_norm"]["scale"].shape == (128,)
    under_norm = [line for line in hlo.splitlines()
                  if "attn_proj/q_norm" in line and "8192" in line]
    assert not under_norm, under_norm[:3]
    assert "attn_proj/k_norm" in hlo  # k's stays XLA's
    rotated = [line for line in hlo.splitlines()
               if "attn_proj/concatenate" in line
               and re.search(r"\[1,8192,(32,128|32,64|4096)\]", line)]
    assert not rotated, rotated[:3]
    assert not re.findall(r"= f32\[(?:1,)?8192,4096\]\S* copy\(", hlo)
    # "Δ at the door": the backward's row statistic is dQ's, so no float32
    # array of q's width stands in front of the backward kernels
    assert not re.search(r"bqhd,bqhd->bhq|bhqd,bhqd->bhq", hlo)
    assert not re.findall(r"f32\[(?:1,)?8192,(?:4096|32,128)\]", hlo)


def test_packed_d64_layer_leaves_the_row_statistic_to_dq(v5e_topology, v5e):
    """One ``MultiHeadAttention`` of GPT-2-small (16 x 1,024 x 768, 12
    heads of 64, packed), ``value_and_grad`` compiled: three Mosaic calls,
    and no ``rowsum(g x out)`` of XLA's (until PR 49 a second output of the
    ``out`` projection's backward fusion): the statistic is dQ's."""
    from horovod_tpu.models.transformer import (
        MultiHeadAttention, TransformerConfig,
    )

    attn = MultiHeadAttention(TransformerConfig(
        d_model=768, n_heads=12, causal=True, dtype=jnp.bfloat16,
    ))
    x = jax.ShapeDtypeStruct((16, 1024, 768), jnp.bfloat16)

    def loss(params, x):
        return attn.apply(params, x).astype(jnp.float32).sum()

    params, hlo = _compile_layer_grad(v5e_topology, v5e, attn, x, loss)
    assert hlo.count("tpu_custom_call") == 3
    assert not re.search(r"bqhd,bqhd->bhq|bhqd,bhqd->bhq", hlo)
    # Δ' goes from dQ to dK/dV as the statistics lie
    (dq,) = re.findall(r"(%\S*hvd_flash_bwd_dq\S*) = \(", hlo)
    assert re.search(
        rf"f32\[16,12,8,1024\]\S* get-tuple-element\({re.escape(dq)}\), "
        r"index=1", hlo,
    )


def test_reglu_expert_layer_compiles_at_the_window_cell_shapes(v5e):
    """The expert layer of the window cell: 16,384 tokens, top-6 of 64 by
    the softmax over the chosen, 8 ReLU-gated experts held at 2560 x 768:
    PR 39's batched dots at their second shape."""
    from horovod_tpu.parallel import ep

    def loss(x, router, gate, up, down):
        chosen, weights = ep.topk_route(
            x, router, None, top_k=6, scoring="softmax"
        )
        out = ep.local_experts(
            x, chosen, weights, gate, up, down, first_expert=0,
            n_experts=64, activation="relu",
        )
        return out.astype(jnp.float32).sum()

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), v5e,
        ((16384, 2560), jnp.bfloat16), ((2560, 64), jnp.float32),
        ((8, 2560, 768), jnp.float32), ((8, 2560, 768), jnp.float32),
        ((8, 768, 2560), jnp.float32),
    )
    assert "conditional" not in hlo and "while" not in hlo
    entry = hlo[hlo.index("\nENTRY"):]
    assert not re.findall(r"= bf16\[8,16384,2560\]", entry)
    # the stacks are read as stored: no copy of an expert stack
    assert not re.findall(r"= f32\[8,(?:768,2560|2560,768)\]\S* copy\(",
                          entry)


def test_latent_attention_builds_no_keys_around_the_kernels(v5e_topology, v5e):
    """One ``LatentAttention``'s ``value_and_grad`` at the expert cell's
    shape, compiled: what the program did to K and V between ``kv_b`` and
    the kernels is gone by ``op_name`` (the rotary key's broadcast to the
    heads, the keys' concatenation, the split of dK and the sum that
    rebuilt ``[dk_nope | dv]``), and ``kv_b``'s matmul output is the
    forward kernel's operand, no ``copy`` between.  Since PR 41 the same
    holds of q: the kernels rotate it, so neither q's concatenation nor its
    sum in the backward is left, nothing is shaped ``[.., 32, 32, 2]``, and
    ``q_b``'s matmul output is the forward kernel's operand."""
    import re

    from horovod_tpu.models.latent_moe import LatentAttention, LatentMoEConfig

    attn = LatentAttention(LatentMoEConfig())
    x = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16)

    def loss(params, x):
        return attn.apply(params, x).astype(jnp.float32).sum()

    params, hlo = _compile_layer_grad(v5e_topology, v5e, attn, x, loss)
    assert hlo.count("tpu_custom_call") == 3
    entry = hlo[hlo.index("\nENTRY"):]
    defined = {
        m.group(1): line for line in entry.splitlines()
        if (m := re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line))
    }
    def results(label):
        """Result shapes of the entry's instructions labelled ``label``."""
        return [
            line.split(" = ", 1)[1].split(" ", 1)[0]
            for line in defined.values() if f'mla_proj/{label}"' in line
        ]

    assert results("kv_b/dot_general")
    # the rotary key is no longer broadcast to 32 heads
    assert not results("broadcast_in_dim")
    # of the two [.., 32, 192] concatenations neither is left (q's, and
    # its sum in the backward, went in PR 41); dK is not cut into dk_nope
    # and the rotary part, and nothing puts [dk_nope | dv] together for
    # kv_b's backward
    wide = lambda shapes, dims: [s for s in shapes if f"[2,4096,{dims}]" in s]  # noqa: E731
    assert not wide(results("concatenate"), "32,192")
    assert not wide(results("add_any"), "32,192")
    assert "[2,4096,32,32,2]" not in hlo and "[2,4096,32,32,1]" not in hlo
    assert not wide(results("split"), "32,128")
    assert not wide(results("add_any"), "32,256")
    assert not wide(results("add_any"), "8192")
    (fwd,) = [
        line for line in defined.values()
        if "custom-call(" in line and "hvd_flash_fwd" in line.split(" = ")[0]
    ]
    operands = re.findall(r"%([\w.\-]+)", fwd.split("custom-call(", 1)[1].split(")", 1)[0])
    (kv,) = [
        name for name in operands
        if re.search(r"bf16\[2,4096,8192\]", defined[name].split(" = ", 1)[1])
    ]
    assert " copy(" not in defined[kv], defined[kv]
    assert "kv_b/dot_general" in defined[kv], defined[kv]
    (q,) = [  # q, and not the turned q the call itself writes
        name for name in operands
        if re.search(r"bf16\[2,4096,6144\]", defined[name].split(" = ", 1)[1])
    ]
    assert " copy(" not in defined[q], defined[q]
    assert "q_b/dot_general" in defined[q], defined[q]


def test_local_expert_layer_compiles_at_the_cell_buffer_shapes(v5e):
    """The expert layer of the latent-attention cell: 8,192 tokens, top-8
    of 256, 16 experts held at width 2048 x 768, bf16 rows: the three
    matmuls over all 16 x 8,192 rows, forward and backward, and nothing
    whose length could follow the routing."""
    from horovod_tpu.parallel import ep

    def loss(x, router, gate, up, down):
        chosen, weights = ep.topk_route(
            x, router, jnp.zeros((256,), jnp.float32), top_k=8, scale=2.5
        )
        out = ep.local_experts(
            x, chosen, weights, gate, up, down, first_expert=0,
            n_experts=256,
        )
        return out.astype(jnp.float32).sum()

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), v5e,
        ((8192, 2048), jnp.bfloat16), ((2048, 256), jnp.float32),
        ((16, 2048, 768), jnp.float32), ((16, 2048, 768), jnp.float32),
        ((16, 768, 2048), jnp.float32),
    )
    assert "conditional" not in hlo and "while" not in hlo
    # dx is one contraction over (held, F) for gate and one for up: the
    # per-expert partial products are not built in HBM (they are, 537 MB
    # of them, in the backward that autodiff derives from the two dots)
    entry = hlo[hlo.index("\nENTRY"):]
    assert not re.findall(r"= bf16\[16,8192,2048\]", entry)


def test_expert_stacks_and_their_moments_are_read_as_stored(v5e):
    """One AdamW step over the three expert stacks at the cell's shapes,
    state donated: gate and up, and their dW, are dots batched over the
    expert axis, so the compiler reads ``[16, 2048, 768]`` as it is stored
    and turns neither the weights nor their moments round and back (the
    merged ``td,edf->tef`` form wanted ``{1,2,0}``: 12 such copies a
    layer)."""
    import optax

    from horovod_tpu.parallel import ep

    optimizer = optax.adamw(3e-4)

    def step(stacks, opt_state, x, chosen, weights):
        def loss(stacks):
            out = ep.local_experts(
                x, chosen, weights, *stacks, first_expert=0, n_experts=256
            )
            return out.astype(jnp.float32).sum()

        value, grads = jax.value_and_grad(loss)(stacks)
        updates, opt_state = optimizer.update(grads, opt_state, stacks)
        return optax.apply_updates(stacks, updates), opt_state, value

    placed = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e
    )
    stacks = tuple(
        placed(shape, jnp.float32)
        for shape in ((16, 2048, 768), (16, 2048, 768), (16, 768, 2048))
    )
    opt_state = jax.tree.map(
        lambda a: placed(a.shape, a.dtype),
        jax.eval_shape(optimizer.init, stacks),
    )
    hlo = jax.jit(step, donate_argnums=(0, 1)).lower(
        stacks, opt_state, placed((8192, 2048), jnp.bfloat16),
        placed((8192, 8), jnp.int32), placed((8192, 8), jnp.float32),
    ).compile().as_text()
    stack = r"f32\[16,(?:2048,768|768,2048)\]\{([\d,]*)[^}]*\}"
    assert not re.findall(rf"= {stack} copy\(", hlo)
    entry = hlo[hlo.index("\nENTRY"):]
    layouts = re.findall(rf"= {stack} parameter\(", entry)
    assert len(layouts) == 9 and set(layouts) == {"2,1,0"}, layouts
    # gate / up / down, gate and up again in the backward, then dhidden,
    # down's dW and ONE dW for gate and up: the merged form's nine
    # matmuls' work in eight dots
    assert len(re.findall(r" convolution\(", hlo)) == 8
    assert "conditional" not in hlo and "while" not in hlo


# name: (heads, sq, skv, d, dtype, causal)
_BHSD_CASES = {
    "d16-causal": (4, 512, 512, 16, jnp.bfloat16, True),
    "d32-float32": (4, 512, 512, 32, jnp.float32, False),
    "d80-causal": (4, 1024, 1024, 80, jnp.bfloat16, True),
    "d128-causal": (6, 1024, 1024, 128, jnp.bfloat16, True),
    "d128": (6, 512, 512, 128, jnp.bfloat16, False),
    "padded-s200": (4, 200, 200, 64, jnp.bfloat16, False),
    "cross-sq72-skv200": (4, 72, 200, 64, jnp.bfloat16, False),
    "cross-sq640-skv1000-causal": (4, 640, 1000, 64, jnp.bfloat16, True),
    "cross-float32-sq136-skv40": (2, 136, 40, 32, jnp.float32, False),
}


@pytest.mark.parametrize("case", list(_BHSD_CASES))
def test_flash_attention_bhsd_shapes_compile(v5e, case):
    """The head-major fallback, forward and both backward kernels under a
    cotangent for ``lse`` too, at what no model here runs: head widths
    under, off and at the 128 lanes, float32, K/V lengths that are no
    multiple of 128 (dK/dV's ``[d, block_k]`` accumulators then have a
    ragged lane axis and are turned back ragged) and differ from the q
    length, causal slabs over a padded K/V."""
    heads, sq, skv, d, dtype, causal = _BHSD_CASES[case]

    def loss(q, k, v):
        out, lse = pk.flash_attention_with_lse(
            q, k, v, causal=causal, layout="bhsd", interpret=False
        )
        lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
        return out.astype(jnp.float32).sum() + (lse ** 2).sum()

    hlo = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), v5e,
        ((2, heads, sq, d), dtype), ((2, heads, skv, d), dtype),
        ((2, heads, skv, d), dtype),
    )
    assert hlo.count("tpu_custom_call") >= 3  # fwd + dkdv + dq


def test_fused_adamw_compiles(v5e):
    n = 124 * 1024 * 1024  # a GPT-2-small-sized fp32 flat buffer

    def update(p, m, v, g, count):
        return pk.fused_adamw_update_pallas(
            p, m, v, g, count, lr=3e-4, interpret=False
        )

    buf = ((n,), jnp.float32)
    hlo = _compile(update, v5e, buf, buf, buf, buf, ((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_int8_matmul_compiles(v5e):
    def mm(x, w, s):
        return pk.int8_matmul_pallas(x, w, s, interpret=False)

    hlo = _compile(
        mm, v5e,
        ((16384, 768), jnp.bfloat16),
        ((768, 3072), jnp.int8),
        ((3072,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_fp8_matmul_compiles(v5e):
    def mm(x, w, s):
        return pk.fp8_matmul_pallas(x, w, s, interpret=False)

    hlo = _compile(
        mm, v5e,
        ((16384, 768), jnp.float8_e4m3fn),
        ((768, 3072), jnp.float8_e4m3fn),
        ((), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "wire_dtype,qmax,integer",
    [(jnp.int8, 127.0, True), (jnp.float8_e4m3fn, 448.0, False)],
    ids=["int8", "fp8"],
)
def test_blockwise_quantize_dequantize_compile(v5e, wire_dtype, qmax, integer):
    def quant(rows):
        return pk.quantize_blockwise_pallas(
            rows, qmax=qmax, wire_dtype=wire_dtype, integer=integer,
            interpret=False,
        )

    def dequant(q, s):
        return pk.dequantize_blockwise_pallas(q, s, interpret=False)

    nb, block = 65536, 256
    assert "tpu_custom_call" in _compile(
        quant, v5e, ((nb, block), jnp.float32)
    )
    assert "tpu_custom_call" in _compile(
        dequant, v5e, ((nb, block), wire_dtype), ((nb,), jnp.float32)
    )


# -- the data-parallel step's gradient exchange (PR 29) ----------------------


def _described_lm_step(topo, n_devices):
    """A replicated ``make_train_step`` of a small ``transformer_lm`` (GPT-2
    blocks, tied head) over ``n_devices`` described chips, every default,
    with abstract state and batch placed on the mesh. Every matrix of a
    block passes the exchange's size rule (512 x 512 float32 = 1 MiB)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel
    from horovod_tpu.parallel import dp

    hvd.init(devices=topo.devices[:n_devices])
    mesh = hvd.mesh()
    model = GPT2LMModel(GPT2Config(
        vocab_size=2048, max_len=256, d_model=512, n_heads=8, n_layers=4,
        d_ff=2048,
    ))

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits = model.apply({"params": params}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]
        ).mean()

    step, wrapped = dp.make_train_step(loss_fn, optax.adamw(3e-4))
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    state = jax.eval_shape(lambda p: dp.init_state(p, wrapped), params)

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
            tree,
        )

    batch = {"tokens": jax.ShapeDtypeStruct((8 * n_devices, 257), jnp.int32)}
    return step, placed(state, P()), placed(batch, P(hvd.WORLD_AXIS))


def test_replicated_step_hides_its_gradient_exchange(v5e_topology):
    """On four chips the default step's gradient all-reduces compile to
    asynchronous pairs with work between start and done: at least 90% of
    the gradient bytes, each pair around a matmul or an ``hvd_update``
    fusion, and no synchronous all-reduce over the size rule."""
    import horovod_tpu as hvd
    from horovod_tpu.analysis import collective_schedule
    from horovod_tpu.ops.layout import ASYNC_LEAF_BYTES

    try:
        step, state, batch = _described_lm_step(v5e_topology, 4)
        hlo = step.lower(state, batch).compile().as_text()
    finally:
        hvd.shutdown()
    sched = collective_schedule(hlo)
    assert sched["n_async"] >= 20, sched["n_async"]
    assert sched["async_bytes_share"] >= 0.9, sched["async_bytes_share"]
    for pair in sched["async"]:
        assert pair["index"] < pair["done_index"]
        assert pair["matmuls_between"] + pair["updates_between"] >= 1, pair
    assert all(r["bytes"] < ASYNC_LEAF_BYTES for r in sched["sync"]), (
        sched["sync"]
    )


def test_replicated_step_on_one_device_is_the_plain_program(v5e_topology):
    """With one device on the reduction axis the step is built as it always
    was: no compiler option, no collective, and text identical to a plain
    ``jax.jit`` of the same mapped function."""
    import horovod_tpu as hvd
    from horovod_tpu.analysis import collective_schedule

    try:
        step, state, batch = _described_lm_step(v5e_topology, 1)
        hlo = step.lower(state, batch).compile().as_text()
        plain = jax.jit(
            step._mapped_for(state), donate_argnums=(0,)
        ).lower(state, batch).compile().as_text()
    finally:
        hvd.shutdown()
    assert hlo == plain
    sched = collective_schedule(hlo, scope=None)
    assert sched["n_sync"] == sched["n_async"] == 0
    assert "async-collective" not in hlo
