"""Flash-attention kernel numerics (reference analogue: tests/unit/ops/
accelerators kernel-vs-reference comparisons).

On the CPU test mesh the Pallas TPU kernel can't lower, so these tests run it
in interpreter mode — slow but bit-accurate to the kernel's math. Real-TPU
numerics were validated on hardware (max err ~1e-2 vs einsum at bf16-matmul
precision); see .claude/skills/verify/SKILL.md.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.ops.pallas.flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    if jax.default_backend() != "tpu":
        from jax.experimental import pallas as pl

        monkeypatch.setattr(fa.pl, "pallas_call",
                            functools.partial(pl.pallas_call, interpret=True))
    yield


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    B, T, H, D = 1, 256, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = fa.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2)


def test_gradients_match_reference():
    B, T, H, D = 1, 256, 2, 64
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    g = jax.random.normal(kg, (B, T, H, D), jnp.float32)

    def mk_loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    g1 = jax.grad(mk_loss(functools.partial(fa.flash_attention, causal=True,
                                            block_q=128, block_k=128)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(mk_loss(functools.partial(fa.mha_reference, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-2)


def test_uneven_blocks():
    """T not divisible by the preferred block → _pick_block fallback."""
    B, T, H, D = 1, 192, 1, 64  # 192 = 64*3, not divisible by 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = fa.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2)


# ------------------------------- the causal forward: one kernel, wide steps
def _walk(rows, n_sub):
    """The sub-blocks the forward's grid runs, by the kernel's own rule: a
    step (q block i, span s) runs min(i - s * n_sub, n_sub) sub-blocks of
    its span without a mask and, where that is under n_sub, one more
    masked."""
    run, masked = [], []
    for i, s in zip(*fa._causal_spans(rows, n_sub)):
        interior = min(i - s * n_sub, n_sub)
        run += [(i, s * n_sub + j) for j in range(interior)]
        if interior < n_sub:
            masked.append((i, s * n_sub + interior))
    return run, masked


# the STRUCTURE of every length a cell runs, met in 128-row blocks (the
# least Mosaic takes; interpret mode would take less, flash_supports not):
# 1, 2, 3, 4, 7 and 64 q blocks; 12 = three spans of four, so a span ends
# mid-length; 9 = three spans of three, 7 = two of four, the second ending
# past the keys; 1,000 padded to 1,024; q.k at 192 with v at 128 (latent
# attention), 96 / 96
@pytest.mark.parametrize("T,D,Dv", [
    (128, 32, 32), (256, 32, 32), (384, 32, 32), (512, 32, 32),
    (896, 32, 32), (8192, 16, 16), (1536, 32, 32), (1152, 32, 32),
    (1000, 32, 32), (512, 192, 128), (512, 96, 96)],
    ids=lambda x: str(x))
def test_causal_forward_in_wide_steps_matches_reference(T, D, Dv):
    """Output and log-sum-exp of the one causal forward against the plain
    softmax, gradients through it and the one causal backward, and the
    plan's counts against a walk of the grid it makes."""
    B, H = 1, (1 if T > 2048 else 2)
    keys = jax.random.split(jax.random.PRNGKey(T + D), 4)
    q, k, v, g = (jax.random.normal(key, (B, T, H, w), jnp.float32)
                  for key, w in zip(keys, (D, D, Dv, Dv)))
    plan = fa.flash_forward_plan(T, D, Dv, q.dtype, 128, 128)
    rows = -(-T // 128)
    assert plan[:3] == (128, -(-rows // -(-rows // 4)) * 128, 128)
    run, masked = _walk(rows, plan.span // plan.sub_block)
    assert sorted(run + masked) == [(i, j) for i in range(rows)
                                    for j in range(i + 1)]
    assert masked == [(i, i) for i in range(rows)]
    assert (plan.sub_blocks_run, plan.sub_blocks_masked) == (
        len(run) + len(masked), len(masked))
    assert plan.grid_steps == len(fa._causal_spans(
        rows, plan.span // plan.sub_block)[0])

    attend = jax.jit(functools.partial(fa.flash_attention, block_q=128,
                                       block_k=128))
    scale = D ** -0.5

    @jax.jit
    def plain(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        logits = jnp.where(jnp.tril(jnp.ones((T, T), bool)), logits, -jnp.inf)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits), v),
                jax.nn.logsumexp(logits, axis=-1))

    o_ref, lse_ref = plain(q, k, v)
    if T % 128:
        o = attend(q, k, v)
    else:
        o, lse = jax.jit(lambda q, k, v: fa._flash_forward(
            fa._to_bhtd(q * scale), fa._to_bhtd(k), fa._to_bhtd(v),
            1.0, True, 128, 128))(q, k, v)
        o = fa._to_bthd(o, B)
        np.testing.assert_allclose(np.asarray(lse).reshape(B, H, T),
                                   np.asarray(lse_ref), atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-2, rtol=2e-2)
    if D == Dv and T <= 2048:
        loss = lambda f: lambda *a: jnp.sum(f(*a) * g)
        for a, b in zip(jax.grad(loss(attend), argnums=(0, 1, 2))(q, k, v),
                        jax.grad(loss(fa.mha_reference),
                                 argnums=(0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("t,d,dv,plan", [
    # (block_q, span, sub_block, grid_steps, sub_blocks_run, .._masked)
    (1024, 96, 96, (512, 1024, 512, 2, 3, 2)),         # gpt2-760m.train.*
    (1024, 64, 64, (512, 1024, 512, 2, 3, 2)),         # gpt2-xl.train.z3x4
    (768, 64, 64, (256, 768, 256, 3, 6, 3)),           # gpt2-xl.serve.doc.c1
    (896, 64, 64, (128, 512, 128, 10, 28, 7)),
    (2048, 128, 128, (512, 2048, 512, 4, 10, 4)),      # olmoe doc4k.c1
    (4096, 192, 128, (512, 2048, 512, 12, 36, 8)),     # openPangu doc8k.c1
    (16384, 128, 128, (512, 2048, 512, 144, 528, 32)),  # Solar doc32k.c1
    (32768, 128, 128, (512, 2048, 512, 544, 2080, 64)),
], ids=lambda x: str(x))
def test_flash_forward_plan_at_the_cells_shapes(t, d, dv, plan):
    """The static grid at the real shapes: every sub-block at or under the
    diagonal once, one masked a row of them, none above."""
    got = fa.flash_forward_plan(t, d, dv, jnp.bfloat16)
    assert tuple(got) == plan
    run, masked = _walk(t // got.sub_block, got.span // got.sub_block)
    rows = t // got.sub_block
    assert len(run) + len(masked) == rows * (rows + 1) // 2 == got.sub_blocks_run
    assert all(j < i for i, j in run) and all(j == i for i, j in masked)


# ------------------------- the causal backward: one kernel, P recomputed once
@pytest.mark.parametrize("t,d,window,plan", [
    # (block, pairs_run, pairs_masked, matmuls a pair, dq bytes, quarters)
    (1024, 96, None, (512, 3, 2, 5, 393216, 3)),       # gpt2-760m.train.*
    (1024, 64, None, (512, 3, 2, 5, 262144, 3)),       # gpt2-xl.train.z3x4
    (8192, 128, None, (512, 136, 16, 5, 4194304, 3)),  # Trinity's full layers
    (8192, 128, 2048, (512, 70, 28, 5, 4194304, 3)),   # Trinity's window layers
    (16384, 192, None, (512, 528, 32, 5, 12582912, 3)),  # Kimi's latent layers
    (1024, 96, 300, (512, 3, 3, 5, 393216, 3)),        # a window inside a block
    (1000, 32, None, (512, 3, 2, 5, 131072, 3)),       # padded to 1,024
    (896, 64, None, (128, 28, 7, 5, 229376, 4)),       # 128s: no half of whole 128s
], ids=lambda x: str(x))
def test_flash_backward_plan_at_the_cells_shapes(t, d, window, plan):
    """The one causal backward's static grid at the real shapes: every
    block pair at or under the diagonal (inside the band) once, five
    matmuls a pair, none above; T = 1,024 in 512s is 3 pairs where the
    rectangular grid this replaced ran the square's 4."""
    got = fa.flash_backward_plan(t, d, jnp.bfloat16, window)
    assert tuple(got) == plan
    n = -(-t // got.block)
    ki, qi = fa._causal_pairs_colmajor(n, got.block, window)
    assert len(ki) == got.pairs_run and (qi >= ki).all()
    if window is None:
        assert got.pairs_run == n * (n + 1) // 2 and got.pairs_masked == n
    else:
        # the window drops the pairs outside the band, and only those
        assert {(int(k), int(q)) for k, q in zip(ki, qi)} == {
            (k, q) for q in range(n) for k in range(q + 1)
            if q * got.block - (k * got.block + got.block - 1) < window}
    fwd = fa.flash_forward_plan(t, d, d, jnp.bfloat16, window=window)
    assert (got.block, got.pairs_run, got.pairs_masked) == (
        fwd.sub_block, fwd.sub_blocks_run, fwd.sub_blocks_masked)


# the cells' shapes scaled down, their structure kept: T = 1,024 in two
# blocks at d 96 and 64 (the diagonal in quarters), four and more blocks at
# d 128 with and without a window that ends inside a block, q.k 192 with v
# 128 (v, o and dO go in zero-padded), lengths that pad or halve the block
@pytest.mark.parametrize("T,D,Dv,window,block,dtype", [
    (1024, 96, 96, None, 512, "float32"), (1024, 64, 64, None, 512, "bfloat16"),
    (1024, 128, 128, None, 256, "float32"), (1024, 128, 128, 300, 256, "float32"),
    (1280, 128, 128, 700, 256, "bfloat16"), (512, 192, 128, None, 128, "float32"),
    (1024, 192, 128, None, 512, "bfloat16"), (640, 32, 32, None, 512, "float32"),
    (768, 32, 32, None, 512, "float32"), (896, 32, 32, 200, 512, "float32")],
    ids=lambda x: str(x))
def test_the_one_causal_backward_matches_the_reference(T, D, Dv, window,
                                                       block, dtype):
    """dq, dk, dv of the one causal backward kernel against the plain
    softmax's gradients, at the tolerances the pair of kernels was held to
    (float32: the windowed tests' 5e-5 absolute; bf16: 5e-2)."""
    keys = jax.random.split(jax.random.PRNGKey(T + D), 4)
    q, k, v, g = (jax.random.normal(key, (1, T, 2, w), jnp.float32)
                  .astype(dtype) for key, w in zip(keys, (D, D, Dv, Dv)))
    attend = functools.partial(fa.flash_attention, block_q=block,
                               block_k=block, window=window)
    want = functools.partial(_windowed_reference, window=window or T)
    loss = lambda f: lambda *a: jnp.sum((f(*a) * g).astype(jnp.float32))
    tol = dict(atol=5e-5, rtol=0) if dtype == "float32" \
        else dict(atol=5e-2, rtol=5e-2)
    for a, b in zip(jax.grad(loss(attend), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(want), argnums=(0, 1, 2))(q, k, v)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


# sha256 of the jaxpr (kernel body and all) ``flash_attention(q, q, q)`` and
# ``(..., window=300)`` traced to for bf16 (1, 1024, 2, 96) at the commit
# BEFORE the one causal backward (4d2390d; jax 0.9.0). To refresh after a
# change that is MEANT to move the forward: print them from a checkout of
# the parent
FORWARD_DIGESTS = ("4da99ca45b964efd", "61ca224caa6746f3")


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digests are of jax 0.9.0's jaxprs")
@pytest.mark.parametrize("window,name", [(None, "flash_bwd_dkv"),
                                         (300, "flash_bwd_dkv_win")])
def test_the_causal_backward_is_one_call_and_the_forward_is_the_parents(
        window, name, monkeypatch):
    """The gradient of causal self-attention holds TWO ``pallas_call``s: the
    forward, whose jaxpr is the one the parent commit traced, and ONE
    backward call, named for the dkv kernel it is; nothing is named
    ``flash_bwd_dq``."""
    import hashlib
    import re

    monkeypatch.undo()          # traced as a program for the chip traces it
    q = jnp.zeros((1, 1024, 2, 96), jnp.bfloat16)
    attend = functools.partial(fa.flash_attention, window=window)
    grad = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, q, q))
    assert grad.count("pallas_call[") == 2
    names = re.findall(r"name=(\w*flash\w+)", grad)
    assert sorted(names) == sorted([
        "flash_fwd" + ("_win" if window else ""), name]), names
    forward = str(jax.make_jaxpr(attend)(q, q, q))
    assert hashlib.sha256(forward.encode()).hexdigest()[:16] == {
        None: FORWARD_DIGESTS[0], 300: FORWARD_DIGESTS[1]}[window]


def test_the_backward_refuses_a_head_whose_dq_cannot_stay_in_vmem():
    q = jax.ShapeDtypeStruct((1, 262144, 128), jnp.bfloat16)
    stat = jax.ShapeDtypeStruct((1, 1, 262144), jnp.float32)
    with pytest.raises(NotImplementedError, match="keeps in VMEM"):
        jax.eval_shape(lambda q, lse: fa._flash_backward(
            (q, q, q, q, lse), q, 1.0, True, 512, 512), q, stat)


def test_causal_means_one_length():
    """The causal forward is self-attention's: a causal call at two lengths
    is refused by ``flash_supports`` (so a dispatcher takes its einsum
    path) and by the kernel's wrapper, not masked by some convention."""
    assert not fa.flash_supports(256, 128, True)
    q = jnp.zeros((1, 256, 1, 32))
    with pytest.raises(ValueError, match="one length"):
        fa.flash_attention(q, q[:, :128], q[:, :128], causal=True)


# ----------------------------- remat 'attn' keeps what the backward reads
def _remat_cases():
    """(head_dim, T, where, remat, forward kernels in the gradient): every
    head size the cells train at x a length that tiles and one that pads
    (1000 -> 1024) x the kernel called directly (one chip) and inside the
    ``shard_map`` a mesh puts it in; then the two neighbours of 'attn'."""
    cases = [(d, t, where, "attn", 1)
             for d in (64, 96, 128) for t in (256, 1000)
             for where in ("direct", "shard_map")]
    return cases + [(64, 256, "direct", "full", 2),
                    (64, 256, "direct", "attn_mlp", 1)]


@pytest.mark.parametrize(
    "head_dim,T,where,remat,forwards", _remat_cases(),
    ids=lambda x: str(x))
def test_remat_attn_saves_o_and_lse_so_the_forward_runs_once(
        head_dim, T, where, remat, forwards, capsys):
    """A layer under ``remat_wrap(.., 'attn')`` keeps the flash forward's
    ``o`` and log-sum-exp (named INSIDE the custom VJP's forward rule: the
    residuals themselves), so the gradient holds ``flash_fwd`` once a call
    site, not once more in the recompute, and is exactly the un-rematted
    gradient: same kernels, same inputs. 'full' saves nothing and holds two."""
    from jax.ad_checkpoint import print_saved_residuals
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.models import common

    B, H = 2, 2
    keys = jax.random.split(jax.random.PRNGKey(head_dim + T), 4)
    x, wq, wk, wv = (jax.random.normal(k, s, jnp.float32) for k, s in zip(
        keys, [(B, T, H * head_dim)] + [(H * head_dim, H * head_dim)] * 3))
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",)) \
        if where == "shard_map" else None
    spec = P("data")

    def layer(x, wq, wk, wv):
        # q, k, v come from the layer's input, as a block's c_attn makes them
        q, k, v = ((x @ w * 0.05).reshape(B, T, H, head_dim)
                   for w in (wq, wk, wv))
        o = common._kernel_on_mesh(fa.flash_attention, mesh, (q, k, v),
                                   (spec, spec, spec), spec)
        return jnp.sin(o.reshape(B, T, -1)) @ wq

    def grads(fn):
        loss = lambda *a: jnp.sum(fn(*a))
        g = jax.grad(loss, argnums=(0, 1, 2, 3))
        n = str(jax.make_jaxpr(g)(x, wq, wk, wv)).count("name=flash_fwd")
        return jax.jit(g)(x, wq, wk, wv), n

    plain, n_plain = grads(layer)
    kept, n_kept = grads(common.remat_wrap(layer, remat))
    assert (n_plain, n_kept) == (1, forwards)
    for a, b in zip(kept, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if where == "direct" and remat != "full":
        # besides the layer's arguments: o ONCE, in the model's lane-dense
        # (B, T, H*D) layout, and the log-sum-exp at B*H*T float32
        t_run = fa._padded_len(T, fa.DEFAULT_BLOCK_Q)
        capsys.readouterr()
        print_saved_residuals(common.remat_wrap(layer, remat), x, wq, wk, wv)
        saved = sorted(l.split(" ", 1)[0]
                       for l in capsys.readouterr().out.splitlines()
                       if "from the argument" not in l)
        assert saved == sorted([f"f32[{B},{t_run},{H * head_dim}]",
                                f"f32[{B * H},{t_run}]"]), saved


@pytest.mark.parametrize("producer", ["einsum", "ring", "sparse_stand_in"])
def test_an_attention_no_kernel_ran_names_its_output_for_remat_attn(
        producer, capsys, monkeypatch):
    """What 'attn' saves is named where it is made. Off the kernel that is
    three places: the einsum path, the ring, gpt2's off-TPU stand-in for the
    block-sparse kernel. Each keeps ONE tensor of B*T*H*D a layer besides
    the layer's arguments, so the backward re-runs no attention; a new path
    that forgot the name would keep nothing here, and say nothing."""
    from jax.ad_checkpoint import print_saved_residuals
    from jax.sharding import Mesh

    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models import common
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel.topology import ALL_AXES

    B, T, H, D = 2, 64, 2, 16
    if producer == "einsum":
        attend = common.local_causal_attention
    elif producer == "ring":
        shape = [2 if a == "seq" else 1 for a in ALL_AXES]
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(shape), ALL_AXES)
        monkeypatch.setattr(comm, "get_mesh", lambda: mesh)
        attend = functools.partial(common.causal_attention,
                                   sequence_parallel="ring")
    else:
        attend = GPT2Model(GPT2Config(
            vocab_size=64, n_positions=T, n_embd=H * D, n_layer=1, n_head=H,
            sparse_attention={"mode": "fixed", "block": 16,
                              "num_local_blocks": 2}))._sparse_attention
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(keys[0], (B, T, H * D), jnp.float32)
    w = jax.random.normal(keys[1], (H * D, H * D), jnp.float32)

    def layer(x, w):
        q, k, v = ((x @ w * s).reshape(B, T, H, D) for s in (0.1, 0.2, 0.3))
        return jnp.sin(attend(q, k, v).reshape(B, T, -1)) @ w

    def saved(remat):
        capsys.readouterr()
        print_saved_residuals(common.remat_wrap(layer, remat), x, w)
        # (the stand-in also holds its layout's mask, a bool constant)
        return [l.split(" ", 1)[0] for l in capsys.readouterr().out.splitlines()
                if "from the argument" not in l and l.startswith("f32")]

    assert saved("attn") == [f"f32[{B},{T},{H},{D}]"]
    assert saved("full") == []


# ------------------------------------------- the causal WINDOW (PR 37)
def _windowed_reference(q, k, v, window):
    """Plain softmax under the mask ``0 <= i - j < window``."""
    T, d = q.shape[1], q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / np.sqrt(d)
    dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    logits = jnp.where((dist >= 0) & (dist < window), logits, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)


# lengths and windows that are and are not multiples of a block: a window of
# whole blocks (the trailing edge cuts ONE block a row, as the cell's 2,048
# in 512s), of a block and a bit (two), shorter than a block (diagonal and
# trailing edge in one block), a padded length, one block, three spans
@pytest.mark.parametrize("T,window,block", [
    (512, 128, 128), (512, 200, 128), (640, 257, 128), (1024, 256, 128),
    (1024, 384, 128), (300, 77, 128), (256, 64, 256), (1536, 520, 128)],
    ids=lambda x: str(x))
def test_windowed_kernels_match_the_masked_reference(T, window, block):
    """Forward and all three gradients of ``flash_attention(window=)``
    against the plain softmax under the window's mask, and the plan's counts
    against the band it has to cover."""
    keys = jax.random.split(jax.random.PRNGKey(T + window), 4)
    q, k, v, g = (jax.random.normal(key, (2, T, 2, 32), jnp.float32)
                  for key in keys)
    attend = functools.partial(fa.flash_attention, block_q=block,
                               block_k=block, window=window)
    want = functools.partial(_windowed_reference, window=window)
    np.testing.assert_allclose(np.asarray(attend(q, k, v)),
                               np.asarray(want(q, k, v)), atol=2e-5, rtol=0)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * g)
    for a, b in zip(jax.grad(loss(attend), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(want), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=0)
    plan = fa.flash_forward_plan(T, 32, 32, q.dtype, block, block, window)

    def band(sub):
        """(the sub-block pairs of the band, how many of them are masked): a
        pair is run iff some (row, col) of it is in the band, and masked iff
        not all of them are."""
        rows = -(-T // sub)
        inside = lambda i, j: 0 <= i * sub - (j * sub + sub - 1) \
            and i * sub + sub - 1 - j * sub < window
        touched = [(i, j) for i in range(rows) for j in range(i + 1)
                   if i * sub - (j * sub + sub - 1) < window]
        return rows, touched, sum(not inside(i, j) for i, j in touched)

    sub = plan.sub_block
    rows, touched, masked = band(sub)
    assert plan.sub_blocks_run == len(touched)
    assert plan.sub_blocks_masked == masked
    n_sub = plan.span // sub
    qi, si = fa._causal_spans(rows, n_sub, sub, window)
    assert plan.grid_steps == len(qi)
    assert {(i, s) for i, s in zip(qi, si)} == \
        {(i, j // n_sub) for i, j in touched}
    # the backward's list: the band's pairs, each once, and its plan's counts
    # (at its OWN block: a window under half a sub-block narrows the
    # forward's alone)
    back = fa.flash_backward_plan(T, 32, q.dtype, window, block, block)
    rows, touched, masked = band(back.block)
    assert sorted((i, j) for j, i in zip(*fa._causal_pairs_colmajor(
        rows, back.block, window))) == touched
    assert (back.pairs_run, back.pairs_masked) == (len(touched), masked)
    assert back.block == sub or window * fa._WINDOWS_A_SUB_BLOCK <= sub


def test_the_cells_windowed_plan():
    """8,192 tokens under a window of 2,048 in 512s (trinity-mini.train.z1
    .s8k): five sub-blocks a row once the window is full, two of them
    masked, where full attention runs up to sixteen."""
    full = fa.flash_forward_plan(8192, 128, 128, jnp.bfloat16)
    win = fa.flash_forward_plan(8192, 128, 128, jnp.bfloat16, window=2048)
    assert tuple(full) == (512, 2048, 512, 40, 136, 16)
    assert tuple(win) == (512, 2048, 512, 28, 70, 28)
    assert 70 == sum(min(i + 1, 5) for i in range(16))


def test_without_a_window_every_plan_list_and_program_is_the_parents():
    """``window=None`` (and a window that reaches the whole length) gives
    the lists the kernels always had and traces the same program."""
    for rows, n_sub in ((1, 1), (4, 4), (7, 4), (16, 4), (9, 3)):
        qi = np.concatenate([np.full(i // n_sub + 1, i, np.int32)
                             for i in range(rows)])
        si = np.concatenate([np.arange(i // n_sub + 1, dtype=np.int32)
                             for i in range(rows)])
        got = fa._causal_spans(rows, n_sub)
        assert (got[0] == qi).all() and (got[1] == si).all()
    ki, qi = fa._causal_pairs_colmajor(5)
    assert ki.tolist() == [0] * 5 + [1] * 4 + [2] * 3 + [3] * 2 + [4]
    assert qi.tolist() == [0, 1, 2, 3, 4, 1, 2, 3, 4, 2, 3, 4, 3, 4, 4]
    q = jnp.zeros((1, 1024, 2, 32), jnp.float32)
    grad = lambda **kw: jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, block_q=128, block_k=128, **kw)),
        argnums=(0, 1, 2)))(q, q, q)
    plain = str(grad())
    assert str(grad(window=None)) == plain == str(grad(window=1024)) \
        == str(grad(window=5000))
    assert "flash_fwd_win" not in plain and "flash_bwd_dkv_win" not in plain
    windowed = str(grad(window=300))
    for name in ("flash_fwd_win", "flash_bwd_dkv_win"):
        assert name in windowed
    with pytest.raises(ValueError, match="causal window"):
        fa.flash_attention(q, q, q, causal=False, window=8)


def test_a_static_window_goes_to_the_kernel_and_a_traced_one_to_the_einsum(
        monkeypatch):
    """``models/common.py``: on a TPU target a Python-int window is the
    kernel's, a traced scalar (GPT-Neo's mixed scan) the einsum's; on the
    CPU both are the einsum's, with the same numbers."""
    from deepspeed_tpu.models import common

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (1, 256, 2, 32), jnp.float32)
               for key in keys)
    want = np.asarray(_windowed_reference(q, k, v, 100))
    np.testing.assert_allclose(np.asarray(common.causal_attention(
        q, k, v, window=100)), want, atol=2e-5, rtol=0)
    monkeypatch.setattr(common, "_kernel_target", lambda: (None, True))
    static = jax.make_jaxpr(lambda q, k, v: common.causal_attention(
        q, k, v, window=100))(q, k, v)
    assert "flash_fwd_win" in str(static)
    np.testing.assert_allclose(np.asarray(common.causal_attention(
        q, k, v, window=100)), want, atol=2e-5, rtol=0)
    traced = jax.make_jaxpr(lambda q, k, v, w: common.causal_attention(
        q, k, v, window=w))(q, k, v, jnp.int32(100))
    assert "pallas_call" not in str(traced)
