"""Shared model building blocks (chunked LM loss, batch parsing).

The chunked vocab-projection + cross-entropy here is the memory trick the
reference implements as fused softmax-CE CUDA kernels
(csrc/transformer/softmax_kernels.cu): the full (B, T, V) fp32 logits tensor
is never materialized — at V≈50k that is multiple GB per microbatch.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.pallas import SAVED_KDA_STATES, SAVED_LSE, SAVED_O

# logits-buffer budget of chunked_lm_loss: the chunk length is the largest
# divisor of T that keeps ONE CHIP's (B, chunk, V) fp32 logits at or under
# 256MB, B being the rows that chip computes (all of them on one device, its
# own share where the scan runs per chip)
_CHUNK_ELEMS = 64 * 1024 * 1024

NEG_INF_ATTN = -1e30

# How a model that generates by diffusion over blocks denoises a block (the
# model protocol's ``block_decoding``; the inference engine runs it): the
# block's ``length``, the most forward ``steps`` over it, which masked
# positions a pass unmasks — ``sequential``: the leftmost;
# ``low_confidence_static``: those whose chosen token is the most probable;
# ``low_confidence_dynamic``: every one above ``threshold`` if they are at
# least the step's count, else as the static rule — and the ``mask_token_id``
# a position not yet chosen is read as.
BlockDecoding = collections.namedtuple(
    "BlockDecoding", "length steps remasking threshold mask_token_id")
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")
# What such a model's programs sum in their cache's ``block_passes`` leaf, in
# its order: forward passes that denoise, forward passes that ONLY commit,
# blocks whose commit rode in a later block's first pass, blocks finished.
BLOCK_COUNTS = ("passes", "commits", "carried", "blocks")


def layer_scan(body, init, xs, unroll: int = 1):
    """``jax.lax.scan`` over layer-stacked ``xs``: the one call every
    layer-stacked trunk walks its blocks with."""
    return jax.lax.scan(body, init, xs, unroll=max(1, int(unroll)))


# The file's one trace-time hook, over what a block is HANDED: under ZeRO-3 on
# more than one chip the engine installs the placement layer's gather-on-use
# rule (runtime/zero/partition.py::LayerGathers) around the trace of the
# loss's gradient, and `remat_wrap` applies it to the block's arguments
# INSIDE the block's checkpoint. With nothing installed `remat_wrap` traces
# what it always did.
_LAYER_LEAVES_HOOK = None


@contextlib.contextmanager
def layer_leaves_hook(hook):
    """``hook(args) -> args`` over every ``remat_wrap``-ed block traced in
    the body (None: none)."""
    global _LAYER_LEAVES_HOOK
    prev, _LAYER_LEAVES_HOOK = _LAYER_LEAVES_HOOK, hook
    try:
        yield
    finally:
        _LAYER_LEAVES_HOOK = prev


def alibi_slopes(n_head: int):
    """ALiBi per-head slopes, matching HF ``build_alibi_tensor`` (geometric
    sequence on the nearest power of two, interleaved extras otherwise)."""
    cp2 = 2 ** math.floor(math.log2(n_head))
    base = 2.0 ** (-(2.0 ** -(math.log2(cp2) - 3)))
    slopes = [base ** (i + 1) for i in range(cp2)]
    if cp2 != n_head:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * cp2) - 3)))
        slopes += [extra_base ** (i + 1)
                   for i in range(0, 2 * (n_head - cp2), 2)]
    return jnp.asarray(slopes, jnp.float32)


def _scaled_inv_freq(inv_freq, scaling: Optional[dict]):
    """Apply HF-style rope_scaling to the frequency vector."""
    if not scaling:
        return inv_freq
    kind = scaling.get("rope_type", scaling.get("type", "default"))
    if kind == "default":
        return inv_freq
    factor = float(scaling["factor"])
    if kind == "linear":
        return inv_freq / factor
    # "llama3" (3.1+ context extension): low-frequency components divided by
    # `factor`, high-frequency kept, smooth interpolation in between —
    # matching transformers' _compute_llama3_parameters
    low = float(scaling["low_freq_factor"])
    high = float(scaling["high_freq_factor"])
    old_len = float(scaling["original_max_position_embeddings"])
    wavelen = 2.0 * math.pi / inv_freq
    smooth = (old_len / wavelen - low) / (high - low)
    smoothed = (1.0 - smooth) / factor * inv_freq + smooth * inv_freq
    scaled = jnp.where(wavelen > old_len / low, inv_freq / factor, inv_freq)
    is_medium = (wavelen >= old_len / high) & (wavelen <= old_len / low)
    return jnp.where(is_medium, smoothed, scaled)


def _rope_cos_sin(positions, head_dim: int, theta: float,
                  scaling: Optional[dict] = None, interleaved: bool = False):
    """cos/sin tables (T, Dh) for RoPE. ``interleaved=False``: rotate-half
    convention (LLaMA/NeoX — frequency vector duplicated by concatenation);
    ``interleaved=True``: rotate-every-two (GPT-J — each frequency repeated
    for an adjacent dim pair)."""
    d2 = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(d2, dtype=jnp.float32) / d2))
    inv_freq = _scaled_inv_freq(inv_freq, scaling)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]   # (T, d2)
    if interleaved:
        return jnp.repeat(jnp.cos(ang), 2, axis=-1), jnp.repeat(jnp.sin(ang), 2, axis=-1)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    return cos, sin


def apply_rope(x, cos, sin, interleaved: bool = False):
    """x: (B, T, H, Dh); cos/sin: (T, Dh) built with the SAME convention."""
    x32 = x.astype(jnp.float32)
    if interleaved:
        # rotate_every_two: out[2i] = -x[2i+1], out[2i+1] = x[2i]
        x1 = x32[..., ::2]
        x2 = x32[..., 1::2]
        rotated = jnp.stack([-x2, x1], axis=-1).reshape(x32.shape)
    else:
        h1, h2 = jnp.split(x32, 2, axis=-1)
        rotated = jnp.concatenate([-h2, h1], axis=-1)
    out = x32 * cos[None, :, None, :] + rotated * sin[None, :, None, :]
    return out.astype(x.dtype)


def apply_rope_leading(t, cos, sin):
    """The rotary embedding on the first ``cos.shape[-1]`` columns of every
    head of t (B, T, H, Dh); the others pass as they are."""
    r = cos.shape[-1]
    if r == t.shape[-1]:
        return apply_rope(t, cos, sin)
    return jnp.concatenate([apply_rope(t[..., :r], cos, sin), t[..., r:]],
                           axis=-1)


def _kernel_target():
    """``(mesh, on_tpu)`` for the program under trace: the ambient ``with
    mesh:`` every engine traces inside and whether its devices are TPUs —
    which is what decides if a Mosaic kernel can be in the program, also
    when lowering ahead of time for a chip this process does not hold.
    Outside any mesh context it is the default backend."""
    from deepspeed_tpu.sharding.mesh import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None:
        return None, jax.default_backend() == "tpu"
    return mesh, mesh.devices.flat[0].platform == "tpu"


def _attn_axes(mesh, batch: int, n_heads: int):
    """Mesh axes attention is embarrassingly parallel over, as PartitionSpec
    entries ``(batch_entry, head_entry)``: the dp axes when they divide the
    batch, 'tensor' when it divides the heads — the placement the
    surrounding GSPMD program already uses — else replicated (None)."""
    from deepspeed_tpu.parallel.topology import DP_AXES, TENSOR_AXIS

    if mesh is None:
        return None, None
    dp = tuple(a for a in DP_AXES if mesh.shape.get(a, 1) > 1)
    world = math.prod(mesh.shape[a] for a in dp)
    tp = mesh.shape.get(TENSOR_AXIS, 1)
    return (dp if dp and batch % world == 0 else None,
            TENSOR_AXIS if tp > 1 and n_heads % tp == 0 else None)


def _kernel_on_mesh(kernel, mesh, args, in_specs, out_specs):
    """Call a Pallas kernel from a program compiled over ``mesh``. XLA
    cannot partition a Mosaic call (jax refuses to lower one under GSPMD
    on more than one device), so on a multi-device mesh the call sits in a
    ``shard_map`` manual over EVERY mesh axis; on one device, or already
    inside a fully-manual region (Ulysses), it is called directly."""
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty and set(ctx.manual_axes) == set(ctx.axis_names):
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# what remat ``'attn'`` keeps of a block (``remat_wrap``)
SAVED_BY_ATTN = (SAVED_O, SAVED_LSE, SAVED_KDA_STATES)


def remat_wrap(fn, remat):
    """``fn`` (a per-layer function) under the activation-checkpoint policy a
    model's ``remat`` names (reference activation_checkpointing/
    checkpointing.py role); each config's ``VALID_REMAT`` says which it takes.

    ``'attn'`` keeps what a mixer's backward reads and only its forward can
    make: the output (~1 x d a token) and, where a flash kernel ran, its
    log-sum-exp (one float32 a head a token); of a KDA layer the state
    pass's outputs and its state at the end of every group of chunks. Each
    is named where it is made — the kernels' forward rules
    (``ops/pallas/flash_attention.py``, ``ops/pallas/kda.py``), the
    einsum and ring paths below — so the backward re-runs the qkv and MLP
    matmuls and never attention or the state pass: the best FLOPs / HBM
    trade when ``'dots'`` does not fit. ``'attn_mlp'`` also keeps the MLP's
    activation (``mlp_act``): neither attention nor the two fat MLP matmuls
    are re-run, ~8 d^2 of the 12 d^2 a layer recomputed go for 4 d a token
    more HBM.

    Where a hook on the block's leaves is installed (:func:`layer_leaves_hook`:
    ZeRO-3's gather-on-use), it runs on the arguments inside the checkpoint:
    a layer then keeps its SHARDED leaves and the backward gathers again. A
    gather outside it would make the gathered leaves inputs of the
    checkpoint, which the layer scan stacks over all layers. Without a
    ``remat`` the block is checkpointed for the gathered leaves alone."""
    policies = jax.checkpoint_policies
    hook = _LAYER_LEAVES_HOOK
    if hook is not None:
        block = fn
        fn = lambda *args: block(*hook(args))
        if not remat:
            from deepspeed_tpu.runtime.zero.partition import GATHERED_NAME

            return jax.checkpoint(
                fn, policy=policies.save_anything_except_these_names(
                    GATHERED_NAME))
    if remat in (True, "full"):
        return jax.checkpoint(fn, policy=policies.nothing_saveable)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=policies.dots_with_no_batch_dims_saveable)
    if remat == "attn":
        return jax.checkpoint(fn, policy=policies.save_only_these_names(
            *SAVED_BY_ATTN))
    if remat == "attn_mlp":
        return jax.checkpoint(fn, policy=policies.save_only_these_names(
            *SAVED_BY_ATTN, "mlp_act"))
    return fn


def check_flash_block(block):
    """A model config's ``flash_block``: None (the kernel's default) or a
    multiple of 128. A q block is the lane dimension of the blocks the flash
    kernels pass the log-sum-exp in (``ops/pallas/flash_attention.py``): at
    64 no length over one block tiles, and ``local_causal_attention`` would
    take the einsum path for a config that asked for the kernel."""
    if block is not None and (block <= 0 or block % 128):
        raise ValueError(
            f"flash_block={block}: not a multiple of 128 (the flash kernels' "
            "q block is a lane dimension)")


def _softmax_beside_sink(logits, sink):
    """The softmax over the last axis of ``logits`` (float32) with a SINK
    logit beside each row's scores (``sink`` broadcasts against ``logits``
    with a last axis of 1): the concatenated-column definition. The sink
    takes its share of the mass and has no value, so its column is dropped:
    the rows sum to less than 1."""
    both = jnp.concatenate(
        [logits, jnp.broadcast_to(sink, logits.shape[:-1] + (1,))], axis=-1)
    return jax.nn.softmax(both, axis=-1)[..., :-1]


def local_causal_attention(q, k, v, use_flash: bool = True, alibi=None,
                           causal: bool = True, key_padding_mask=None,
                           flash_block=None, window=None, block=None,
                           sink=None):
    """Self-attention on local (unsharded-sequence) q, k, v with equal head
    counts (B, T, H, Dh) — v's head size may differ from q's and k's (latent
    attention: q.k at 192 columns, v at 128): the Pallas flash kernel on
    TPU, XLA einsum for
    what the kernel does not carry (below) and off-TPU (the CPU tests).
    The path is chosen by what the call needs, never by a failure: a kernel
    that does not trace, lower or compile is an error. Causal by default;
    ``causal=False`` is the encoder (BERT) path.

    ``alibi``: optional (H,) per-head slopes; the bias added is
    ``slopes[h] * j`` (key position only) — equivalent to the canonical
    ``slopes * (j - i)`` because per-row constants cancel in softmax, and
    exactly HF BLOOM's ``build_alibi_tensor`` under a full attention mask.
    ``key_padding_mask``: optional (B, T) True=attend. Biased or masked
    attention takes the einsum path (the flash kernel carries neither), and
    so does a non-causal length the kernel cannot tile (q in whole 128s or
    one block: 576 = 9 x 64 is not).
    ``window``: optional sliding window (GPT-Neo local attention, reference
    containers/gptneo.py; a model's window layers): position i attends to j
    with 0 <= i-j < window. A Python int goes to the flash kernel with the
    rest (``flash_attention(window=)``: the band's blocks only, forward and
    backward). It may also be a TRACED scalar so one scanned layer loop can
    mix global and local layers, <=0 meaning global: a kernel's plan is
    static, so that form takes the einsum path.
    ``block``: optional Python int > 1, the BLOCK-causal mask of a model that
    generates by diffusion over blocks: position i attends to j with ``j //
    block <= i // block`` (its own block whole, every earlier one). Forward
    only, no window beside it; the flash kernel carries it where the block
    is a power of two that divides 128 and the length
    (``flash_attention(block=)``), the einsum otherwise.
    ``sink``: optional (H,) learned sink logits of a WINDOW layer: beside
    each row's scaled scores in the softmax's sum, with no value
    (``flash_attention(sink=)``: the windowed forward's initial state; the
    einsum here is the concatenated-column definition, and the one a
    gradient runs through).
    """
    static_window = window is None or (
        isinstance(window, int) and window > 0 and causal)
    if sink is not None and not (static_window and window is not None):
        raise ValueError("a sink goes with a static causal window")
    if block is not None and block <= 1:
        block = None
    if block is not None and (window is not None or not causal):
        raise ValueError("a block-causal mask takes no window beside it")
    if use_flash and alibi is None and key_padding_mask is None \
            and static_window:
        mesh, on_tpu = _kernel_target()
        if on_tpu:
            from deepspeed_tpu.ops.pallas import flash_attention as fa

            kw = ({"block_q": int(flash_block), "block_k": int(flash_block)}
                  if flash_block else {})
            masked = {} if block is None else {"block": block}
            if fa.flash_supports(q.shape[1], k.shape[1], causal, **kw) and (
                    block is None or (fa.block_mask_supports(block)
                                      and q.shape[1] % block == 0)):
                batch, heads = _attn_axes(mesh, q.shape[0], q.shape[2])
                spec = P(batch, None, heads, None)
                if sink is not None:
                    return _kernel_on_mesh(
                        lambda q, k, v, sink: fa.flash_attention(
                            q, k, v, window=window, sink=sink, **kw),
                        mesh, (q, k, v, sink), (spec, spec, spec, P(heads)),
                        spec)
                return _kernel_on_mesh(
                    lambda q, k, v: fa.flash_attention(
                        q, k, v, causal=causal, window=window, **kw,
                        **masked),
                    mesh, (q, k, v), (spec, spec, spec), spec)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    T = q.shape[1]
    if alibi is not None:
        logits = logits + (alibi[None, :, None, None]
                           * jnp.arange(T, dtype=jnp.float32)[None, None, None, :])
    if causal:
        at = jnp.arange(T)
        mask = jnp.tril(jnp.ones((T, T), jnp.bool_)) if block is None \
            else at[None, :] // block <= at[:, None] // block
        logits = jnp.where(mask[None, None], logits, NEG_INF_ATTN)
    if window is not None:
        assert causal, "windowed attention is causal-only"
        w = jnp.asarray(window, jnp.int32)
        ij = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]   # i - j
        wmask = (ij < w) | (w <= 0)                            # w<=0 → global
        logits = jnp.where(wmask[None, None], logits, NEG_INF_ATTN)
    if key_padding_mask is not None:
        keep = jnp.asarray(key_padding_mask).astype(jnp.bool_)
        logits = jnp.where(keep[:, None, None, :], logits, NEG_INF_ATTN)
    if sink is not None:
        probs = _softmax_beside_sink(
            logits, sink.astype(jnp.float32)[None, :, None, None]
        ).astype(q.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return checkpoint_name(jnp.einsum("bhqk,bkhd->bqhd", probs, v), SAVED_O)


def prefill_attention_form(t: int, d: int, rotary_dim, use_flash: bool = True,
                           window=None, block=None) -> str:
    """The form a prefill's GQA layer takes at ``t`` positions, heads ``d``
    wide of which the rotary embedding turns ``rotary_dim`` (None: none),
    under the layer's static ``window`` (a Python int or None) and ``block``:
    ``"fused"``, :func:`prefill_attention` on q, k and v as the projections
    made them, where the program is for a TPU and the flash forward carries
    the call's masks; ``"plain"``, q rotated and K / V repeated in passes
    around :func:`local_causal_attention`, off it (the CPU's einsum) and
    for what the kernel does not carry. By what the call can see, as
    ``local_causal_attention`` chooses."""
    if not (use_flash and _kernel_target()[1]):
        return "plain"
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    masked = block is None or block <= 1 or (
        fa.block_mask_supports(block) and t % block == 0
        and window is None)
    return "fused" if masked and fa.flash_supports(t, t, True) \
        and fa.prefill_supports(d, rotary_dim) else "plain"


def prefill_attention(q, k, v, cos, sin, window=None, block=None, sink=None):
    """The ``"fused"`` form: ``flash_prefill`` (ops/pallas/
    flash_attention.py: forward only) on q (B, T, H, D) NOT rotated, k (B,
    T, KV, D) rotated, v (B, T, KV, Dv), the model's ``cos`` / ``sin`` (T,
    r) or None, the layer's static ``window``, ``block`` and ``sink`` -> (B,
    T, H, Dv). On a mesh the call is manual over the batch and KV-head axes
    that divide them, as ``local_causal_attention``'s is."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    mesh, _ = _kernel_target()
    batch, heads = _attn_axes(mesh, q.shape[0], k.shape[2])
    spec = P(batch, None, heads, None)
    given = {name: (arg, at) for name, arg, at in (
        ("cos", cos, P()), ("sin", sin, P()), ("sink", sink, P(heads)))
        if arg is not None}
    return _kernel_on_mesh(
        lambda q, k, v, *rest: fa.flash_prefill(
            q, k, v, window=window, block=block, **dict(zip(given, rest))),
        mesh, (q, k, v, *(arg for arg, _ in given.values())),
        (spec, spec, spec, *(at for _, at in given.values())), spec)


# ------------------------------------------------------------------ KV cache
# One layout for every family that decodes through ``cached_decode_attention``
# (gpt2, gpt2_moe, llama): ``(L, B, S, W)`` with the KV heads folded into
# the row — KV head ``g`` in columns ``[g * Dh, (g + 1) * Dh)`` — and ``W`` =
# ``KV * Dh`` rounded up to whole 128-lane tiles (zeros in the pad columns).
# A TPU lays an array out with the minor dimension that pads least: at
# ``(.., S, 25, 64)`` that is S, a transposed layout every decode chunk paid
# two relayouts of the whole cache for (PERF.md, PR 25); at ``(.., S, 1664)``
# it is the row itself, which is what the decode kernel's blocks, prefill's
# writes and the per-token update all want.
KV_LANES = 128


def kv_cache_width(n_kv: int, head_dim: int) -> int:
    return -(-n_kv * head_dim // KV_LANES) * KV_LANES


def _row_dims(head_dim, rows):
    """A head's columns in each row array: ``head_dim`` an int (every array
    alike) or one a row array (K rows at the q.k width, V rows at v's)."""
    return (head_dim,) * len(rows) if isinstance(head_dim, int) else head_dim


def init_kv_cache(n_layer: int, batch_size: int, max_len: int, n_kv: int,
                  head_dim, dtype, rows=("k", "v")):
    """``rows``: the row arrays the cache holds, ``head_dim`` a head's
    columns in them (an int, or one a row array where K rows and V rows
    differ in width). A latent-attention model
    holds ONE, a position's ``[c_kv | k_rope]`` row for all heads (``n_kv``
    1, ``head_dim`` its width: 576 values in 640 lanes where K/V of 128
    heads would be 40,960): the same layout, writes and in-place reads.
    ``n_layer``: the layers that HAVE such rows — of a hybrid model its
    softmax layers only; what its other layers keep of a sequence is not a
    row a position and lies beside these arrays in the same dict
    (``models/kda.py::STATE_LEAVES``, ``cache_footprint``). A WINDOW
    layer's rows are a RING (``init_kv_ring``)."""
    return {**{name: jnp.zeros((n_layer, batch_size, max_len, width), dtype)
               for name, width in zip(rows, (
                   kv_cache_width(n_kv, d)
                   for d in _row_dims(head_dim, rows)))},
            "pos": jnp.zeros((), jnp.int32)}


def init_kv_ring(n_layer: int, batch_size: int, window: int, n_kv: int,
                 head_dim, dtype):
    """What the WINDOW layers of a model that gives them a cache of their
    own keep of a sequence: the last ``window`` positions' rows, ``win_k`` /
    ``win_v`` (L_win, B, window, W) in ``init_kv_cache``'s layout, a RING:
    position p lies in slot ``p % window`` (``kv_ring_write``), and K is
    rotated before it is written, so the order of the slots means nothing
    to the softmax. Bytes a SEQUENCE, whatever its length (``cache_ring``)."""
    held = init_kv_cache(n_layer, batch_size, window, n_kv, head_dim, dtype)
    return dict(zip(CACHE_RING_ROWS, (held["k"], held["v"])))


def kv_cache_partition_specs(n_kv: int, head_dim, rows=("k", "v")):
    """Heads over 'tensor' where the rows carry no pad columns (a padded row
    cut into equal shards would cut through heads); replicated otherwise."""
    from deepspeed_tpu.parallel.topology import TENSOR_AXIS

    heads = TENSOR_AXIS if all((n_kv * d) % KV_LANES == 0
                               for d in _row_dims(head_dim, rows)) else None
    return {**{name: P(None, None, None, heads) for name in rows},
            "pos": P()}


# The row arrays ``init_kv_cache`` makes, by the names the models give them:
# rows a POSITION, (L, B, S, W). What a sequence keeps whatever its length
# is ``models/kda.py::STATE_LEAVES``, (L, B, ...). Anything else in a cache
# dict (``pos``, a counter) is neither.
CACHE_POSITION_ROWS = ("k", "v", "kv")
# ... and the rings of ``init_kv_ring``: rows a position too, but only the
# last ``window`` of them, so bytes a SEQUENCE
CACHE_RING_ROWS = ("win_k", "win_v")


def cache_footprint(cache):
    """(bytes ONE position of one sequence holds across the layers, pad lanes
    and all; bytes one sequence holds whatever its length) of a cache dict,
    from the shapes of its own leaves."""
    from deepspeed_tpu.models.kda import STATE_LEAVES

    held = lambda names: [cache[n] for n in names if n in cache]
    return (sum(x.shape[0] * x.shape[3] * x.dtype.itemsize
                for x in held(CACHE_POSITION_ROWS)),
            sum(x.size // x.shape[1] * x.dtype.itemsize
                for x in held(STATE_LEAVES)))


def cache_ring(cache):
    """(bytes ONE sequence's rings hold across the window layers, pad lanes
    and all, whatever its length; the slots of a ring) of a cache dict;
    (0, 0) where it holds none."""
    held = [cache[n] for n in CACHE_RING_ROWS if n in cache]
    return (sum(x.size // x.shape[1] * x.dtype.itemsize for x in held),
            held[0].shape[2] if held else 0)


def kv_cache_rows(t, max_len: int):
    """Prefill's k or v (B, T, KV, Dh) as one layer of the cache:
    (B, max_len, W), zeros past T and in the pad columns."""
    B, T, KV, Dh = t.shape
    return jnp.pad(t.reshape(B, T, KV * Dh),
                   ((0, 0), (0, max_len - T),
                    (0, kv_cache_width(KV, Dh) - KV * Dh)))


def kv_cache_write(cache, t, layer, pos):
    """k or v (B, T, KV, Dh) — the new token's, or a prompt's — into slots
    ``pos .. pos + T - 1`` of ``layer`` of the stacked cache, in place when
    the cache is a loop carry."""
    B, T, KV, Dh = t.shape
    return jax.lax.dynamic_update_slice(
        cache, t.reshape(1, B, T, KV * Dh).astype(cache.dtype),
        (layer, 0, pos, 0))


def kv_ring_write(ring, t, layer, pos):
    """k or v (B, T, KV, Dh) at positions ``pos .. pos + T - 1`` into
    ``layer`` of a ring (``init_kv_ring``): position p into slot ``p %
    window``. One position (a decode step; ``pos`` traced) is one slot; a
    prompt (``pos`` 0, T static) leaves its last ``window`` positions, each
    in its slot."""
    B, T, KV, Dh = t.shape
    window = ring.shape[2]
    if T == 1:
        return kv_cache_write(ring, t, layer, pos % window)
    if T > window:      # position T - window + i into slot (T - window + i) % window
        t = jnp.roll(t[:, T - window:], (T - window) % window, axis=1)
    return kv_cache_write(ring, t, layer, 0)


def read_as_stored(w):
    """A (K, N) layer slice of a stacked weight, inside a decode loop, kept
    in the layout its parameter has on the device. A TPU stores an array
    with the minor dimension that pads (8, 128) tiles least: a stacked
    (L, 6400, 1600) sits K-minor, the loop's one-row matmul asks for it
    N-minor, and XLA relays the WHOLE stack out ahead of the loop on every
    call (983 MB a decode chunk at gpt2-xl; PERF.md, PR 25). Pinning the
    slice makes the contraction read what is stored. Only where that
    storage is certain: an unsharded weight whose K fills whole lane tiles
    and whose N does not; anything else is left to XLA."""
    K, N = w.shape
    mesh, on_tpu = _kernel_target()
    if not on_tpu or K % KV_LANES or not N % KV_LANES:
        return w
    from deepspeed_tpu.parallel.topology import TENSOR_AXIS

    if mesh is not None and mesh.shape.get(TENSOR_AXIS, 1) > 1:
        return w
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(w, Layout(major_to_minor=(1, 0)))


def cached_decode_attention(q, k_cache, v_cache, layer, pos, n_kv: int,
                            alibi=None, window=None, early=None, v_dim=None,
                            sink=None):
    """Single-token decode attention over the stacked KV cache
    (``init_kv_cache``), shared by the model families. q: (B, H, Dh) — the
    new token's queries; caches (L, B, S, W), ``layer`` of them valid
    through slot ``pos`` (both traced scalars); ``n_kv`` may divide H
    (GQA); ``alibi``: optional (H,) slopes (key-position bias); ``window``:
    optional traced sliding window (GPT-Neo). → (B, H, Dh).

    q may also be (B, Lb, H, Dh): the queries of ``Lb`` positions that see
    slots ``0 .. pos`` and nothing else (a block-diffusion step's block,
    which lies in the last ``Lb`` of those slots: no mask among its
    positions); → (B, Lb, H, Dh). Neither a bias nor a window goes with it.
    ``early`` (with such a q alone): ``(n, last)``, the first ``n`` (static)
    of the positions see slots ``0 .. last`` (traced, <= pos) only: the
    block BEFORE the step's, carried in the same pass and blind to the new
    block's slots.

    ``v_dim``: a head's columns in ``v_cache`` where they are not ``Dh``
    (q.k at 192, v at 128: the two caches differ in width) -> (B, H, v_dim).
    ``sink``: (H,) a head's learned sink logit, beside the scaled scores in
    the softmax's sum, with no value. A window layer's RING
    (``init_kv_ring``) is attended whole: the caller hands ``pos`` as its
    last valid slot, ``min(pos, window - 1)``, and no ``window``.

    The path is chosen the way ``local_causal_attention`` chooses flash:
    the Pallas streaming kernel (ops/pallas/decode_attention.py), which
    reads only slots ``0..pos``, where the program is for a TPU and the
    call carries neither a bias nor a window; the XLA einsum over the whole
    allocation otherwise — and as the reference the kernel is tested
    against. A kernel that does not lower is an error, not a reason to run
    something else.
    """
    if q.ndim == 4:
        if alibi is not None or window is not None:
            raise ValueError("a block of query positions takes neither a "
                             "bias nor a window")
        return _cached_block_attention(q, k_cache, v_cache, layer, pos, n_kv,
                                       early)
    if early is not None:
        raise ValueError("early: of a block of query positions")
    B, H, Dh = q.shape
    extra = {**({} if v_dim is None else {"v_dim": v_dim}),
             **({} if sink is None else {"sink": sink})}
    if alibi is None and window is None:
        mesh, on_tpu = _kernel_target()
        if on_tpu:
            return _decode_kernel_on_mesh(mesh, q, k_cache, v_cache, layer,
                                          pos, n_kv, **extra)
    S = k_cache.shape[2]
    layer_of = lambda c, d=Dh: jax.lax.dynamic_index_in_dim(
        c, layer, 0, keepdims=False)[..., :n_kv * d].reshape(B, S, n_kv, d)
    k_l, v_l = layer_of(k_cache), layer_of(v_cache, v_dim or Dh)
    qg = q.reshape(B, n_kv, H // n_kv, Dh)
    scale = 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k_l).astype(jnp.float32) * scale
    if alibi is not None:
        s = s + (alibi.reshape(n_kv, H // n_kv)[None, :, :, None]
                 * jnp.arange(S, dtype=jnp.float32)[None, None, None, :])
    valid = (jnp.arange(S) <= pos)[None, None, None]
    if window is not None:
        # GPT-Neo local attention: the new token (position `pos`) sees only
        # the last `window` cache slots; window<=0 (traced) means global
        w = jnp.asarray(window, jnp.int32)
        valid = valid & (((jnp.arange(S) > pos - w) | (w <= 0))[None, None, None])
    s = jnp.where(valid, s, NEG_INF_ATTN)
    if sink is not None:
        p = _softmax_beside_sink(s, sink.astype(jnp.float32).reshape(
            1, n_kv, H // n_kv, 1)).astype(q.dtype)
    else:
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrk,bkgd->bgrd", p, v_l).reshape(B, H, -1)


def _decode_kernel_on_mesh(mesh, q, k_cache, v_cache, layer, pos, n_kv: int,
                           early=None, v_dim=None, sink=None):
    """``decode_attn`` (ops/pallas/decode_attention.py) on q (B, H, Dh) or
    (B, Lb, H, Dh), from a program compiled over ``mesh``."""
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    batch, heads = _attn_axes(mesh, q.shape[0], n_kv)
    if (n_kv * q.shape[-1]) % KV_LANES or (n_kv * (v_dim or 0)) % KV_LANES:
        heads = None        # padded rows stay whole (see the specs)
    local_kv = n_kv // (mesh.shape[heads] if heads else 1)
    cache_spec = P(None, batch, None, heads)
    q_spec = P(batch, *([None] * (q.ndim - 3)), heads, None)
    kernel, scalars = functools.partial(decode_attention, n_kv=local_kv), \
        (layer, pos)
    if early is not None:   # the early positions' last slot: traced, as pos
        kernel = lambda q, k, v, layer, pos, last: decode_attention(
            q, k, v, layer, pos, n_kv=local_kv, early=(early[0], last))
        scalars += (early[1],)
    if v_dim is not None or sink is not None:   # a window model's two kinds
        sinks = () if sink is None else (sink,)
        kernel = lambda q, k, v, layer, pos, *b: decode_attention(
            q, k, v, layer, pos, n_kv=local_kv, v_dim=v_dim,
            **({"sink": b[0]} if b else {}))
        return _kernel_on_mesh(
            kernel, mesh, (q, k_cache, v_cache) + scalars + sinks,
            (q_spec, cache_spec, cache_spec, P(), P())
            + (P(heads),) * len(sinks), q_spec)
    return _kernel_on_mesh(
        kernel, mesh, (q, k_cache, v_cache) + scalars,
        (q_spec, cache_spec, cache_spec) + (P(),) * len(scalars), q_spec)


def _cached_block_attention(q, k_cache, v_cache, layer, pos, n_kv: int,
                            early=None):
    """``cached_decode_attention`` for q (B, Lb, H, Dh): the kernel where
    the program is for a TPU, else its einsum twin (and test reference):
    every one of the ``Lb`` positions over slots ``0 .. pos``, the first
    ``early[0]`` of them over slots ``0 .. early[1]``."""
    B, Lb, H, Dh = q.shape
    mesh, on_tpu = _kernel_target()
    if on_tpu:
        return _decode_kernel_on_mesh(mesh, q, k_cache, v_cache, layer, pos,
                                      n_kv, early)
    S = k_cache.shape[2]
    layer_of = lambda c: jax.lax.dynamic_index_in_dim(
        c, layer, 0, keepdims=False)[..., :n_kv * Dh].reshape(B, S, n_kv, Dh)
    k_l, v_l = layer_of(k_cache), layer_of(v_cache)
    qg = q.reshape(B, Lb, n_kv, H // n_kv, Dh)
    s = jnp.einsum("blgrd,bkgd->bglrk", qg, k_l).astype(jnp.float32) \
        / math.sqrt(Dh)
    last = pos if early is None else jnp.where(
        jnp.arange(Lb) < early[0], early[1], pos)[:, None, None]
    s = jnp.where(jnp.arange(S) <= last, s, NEG_INF_ATTN)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bglrk,bkgd->blgrd", p, v_l).reshape(B, Lb, H, Dh)


def latent_decode_attention(q, cache, layer, pos, v_width: int, scale: float):
    """Single-token decode attention of latent attention (MLA), absorbed:
    the scores AND the weighted sum are taken over the cached latent rows
    themselves. q: (B, H, C) — every head's query already multiplied into
    the row's columns (``[q_nope W_UK^T | q_rope]``); ``cache`` (L, B, S, W)
    the stacked latent cache (``init_kv_cache`` with one row array),
    ``layer`` of it valid through slot ``pos``; the value of a position is
    the first ``v_width`` columns of the SAME row (``c_kv``). -> (B, H,
    v_width). The path is chosen as ``cached_decode_attention`` chooses:
    the Pallas kernel (``latent_decode_attn``: the row read from HBM once
    for all heads, slots ``0..pos`` only) where the program is for a TPU,
    the einsum over the whole allocation otherwise, and as the reference
    the kernel is tested against."""
    B, H, C = q.shape
    mesh, on_tpu = _kernel_target()
    if on_tpu:
        from deepspeed_tpu.ops.pallas.decode_attention import \
            latent_decode_attention as kernel

        batch, _ = _attn_axes(mesh, B, 1)
        return _kernel_on_mesh(
            functools.partial(kernel, v_width=v_width, scale=scale), mesh,
            (q, cache, layer, pos),
            (P(batch, None, None), P(None, batch, None, None), P(), P()),
            P(batch, None, None))
    S = cache.shape[2]
    rows = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    s = jnp.einsum("bhc,bkc->bhk", q, rows[..., :C]).astype(jnp.float32) \
        * scale
    s = jnp.where((jnp.arange(S) <= pos)[None, None], s, NEG_INF_ATTN)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bkc->bhc", p, rows[..., :v_width])


def kda_attention(q, k, v, g, beta, state, differentiable: bool = False):
    """The gated delta rule (KDA) over T > 1 positions continuing ``state``:
    the chunked form of ``ops/pallas/kda.py``. q, k, g (B, T, H, dk), v (B,
    T, H, dv), beta (B, T, H), state (B, H, dk, dv) float32 -> (o (B, T, H,
    dv), the state after the last position). The path is chosen as
    ``cached_decode_attention`` chooses: where the program is for a TPU the
    chunks' operands and the state pass are the Pallas kernels
    (``kda_operands_fwd``, which reads q, k, v and g as they are laid out
    here, and ``kda_chunk_fwd``), otherwise their ``jnp`` forms, which are
    also what the kernels are tested against. ``differentiable`` (the trunk
    under ``loss``): both with a backward of their own
    (``ops/pallas/kda.py::operands`` and ``state_pass``: ``kda_operands_bwd``
    and ``kda_chunk_bwd`` on a TPU; off it autodiff of the ``jnp`` operands
    around the state pass's ``jnp`` rule), which keep the operands' inputs
    and the state of every group of chunks, not what a chunk makes on the
    way nor the state of every chunk."""
    from deepspeed_tpu.ops.pallas.kda import chunked_kda

    mesh, on_tpu = _kernel_target()
    if not on_tpu:
        return chunked_kda(q, k, v, g, beta, state, vjp=differentiable)
    batch, heads = _attn_axes(mesh, q.shape[0], q.shape[2])
    rows, held = P(batch, None, heads, None), P(batch, heads, None, None)
    return _kernel_on_mesh(
        functools.partial(chunked_kda, kernel=True, vjp=differentiable), mesh,
        (q, k, v, g, beta, state),
        (rows, rows, rows, rows, P(batch, None, heads), held), (rows, held))


def kda_qkv(p, tail, conv_w, heads: int, eps: float,
            differentiable: bool = False):
    """q, k, v of a KDA mixer from its q | k | v projection's output ``p``
    (B, T, 3 H dk), the convolution's window ``tail`` (B, taps - 1, 3 H dk)
    and taps ``conv_w`` (taps, 3 H dk) float32: convolution, SiLU, unit q
    (x dk^-1/2) and k, ``ops/pallas/kda.py::prepare_qkv`` -> (q, k, v (B, T,
    H, dk) in p's type, the next call's ``tail``). The path is chosen as
    ``kda_attention`` chooses: ONE kernel (``kda_prep_fwd``, which writes
    the rows ``kda_operands_fwd`` reads in place; ``differentiable``: with
    ``kda_prep_bwd`` as its backward, which keeps the three inputs and
    nothing float32) where the program is for a TPU, there is more than one
    position and a head's lanes are whole tiles; otherwise the ``jnp``
    form, which is also what the kernels are tested against: a decode
    step, the CPU, narrow heads."""
    from deepspeed_tpu.ops.pallas import kda

    mesh, on_tpu = _kernel_target()
    if not on_tpu or p.shape[1] == 1 or p.shape[-1] // (3 * heads) % 128:
        return kda.prepare_qkv(p, tail, conv_w, heads, eps)
    batch, _ = _attn_axes(mesh, p.shape[0], heads)
    rows, made = P(batch, None, None), P(batch, None, None, None)
    kernel = kda.prepare if differentiable else kda._prep_kernel
    return _kernel_on_mesh(
        lambda *a: kernel(*a, heads, eps), mesh, (p, tail, conv_w),
        (rows, rows, P(None, None)), (made, made, made, rows))


def causal_attention(q, k, v, use_flash: bool = True, sequence_parallel=False,
                     alibi=None, flash_block=None, window=None, sink=None):
    """The full causal-attention dispatch shared by the model families:
    sequence-parallel (ring / Ulysses over the 'seq' mesh axis) when enabled
    and the mesh has a seq axis, else ``local_causal_attention``."""
    if sequence_parallel:
        if alibi is not None:
            raise NotImplementedError(
                "ALiBi attention does not compose with ring/Ulysses sequence "
                "parallelism (the position bias is not carried across shards)")
        from deepspeed_tpu.comm import comm
        from deepspeed_tpu.parallel import sequence as seq_par

        mesh = comm.get_mesh()
        if mesh.shape.get("seq", 1) > 1:
            if sequence_parallel == "ulysses":
                return seq_par.ulysses_attention(
                    lambda q, k, v: local_causal_attention(
                        q, k, v, use_flash, flash_block=flash_block),
                    q, k, v, mesh)
            # ring attention schedules its own per-shard blocks; the flash
            # tile knob does not apply there
            return checkpoint_name(
                seq_par.ring_attention(q, k, v, mesh, causal=True), SAVED_O)
    return local_causal_attention(q, k, v, use_flash, alibi=alibi,
                                  flash_block=flash_block, window=window,
                                  **({} if sink is None else {"sink": sink}))


def parse_lm_batch(batch):
    """dict with input_ids [+ labels/loss_mask] or bare (B, T) array →
    (ids, labels, loss_mask)."""
    if isinstance(batch, dict):
        ids = batch["input_ids"]
        return ids, batch.get("labels", ids), batch.get("loss_mask")
    return batch, batch, None


def _batch_shard_axes(batch: int):
    """``(mesh, axes)`` when the loss head's scan should run per chip: the
    program is traced under a mesh whose devices are ALL on the batch (dp)
    axes, more than one of them, and their number divides the batch.
    ``(None, None)`` otherwise: one device or no mesh, a mesh that also
    shards the sequence ('seq'), the vocabulary ('tensor') or the layers
    ('pipe'), or a region that is already manual."""
    from deepspeed_tpu.sharding.mesh import ambient_mesh

    mesh = ambient_mesh()
    axes, _ = _attn_axes(mesh, batch, 1)
    if axes is None or jax.sharding.get_abstract_mesh().manual_axes:
        return None, None
    if math.prod(mesh.shape[a] for a in axes) != mesh.size:
        return None, None
    return mesh, axes


def chunked_lm_loss(x, head, targets, loss_mask=None, bias=None, remat=True):
    """Mean next-token NLL with the vocab projection computed in sequence
    chunks.

    x: (B, T, D) final hidden states already shifted to align with
    ``targets`` (B, T); ``head``: (D, V) in compute dtype; ``loss_mask``:
    optional (B, T) weighting. ``remat``: see the scan note below; False
    trades the peak of the saved per-chunk fp32 logits (B*T*V*4 bytes: 1.6G
    at gpt2-760m's 8 x 1023 x 50257) back for ~1% step time — only sensible
    when the model fits HBM with slack.

    Where the rows are computed follows the mesh the program is traced
    under (no option):

    * one device, or no mesh: one scan over all ``B`` rows.
    * a mesh whose devices are all on the batch axes (ZeRO over ``data``,
      with ``mics`` / ``ici`` / ``expert`` where they are there): the scan
      runs per chip, inside a ``shard_map`` over those axes, on the chip's
      OWN ``B / world`` rows — so that is the ``B`` the chunk length is
      sized from. ``head`` (and ``bias``) enter it WHOLE: a ZeRO-3 head is
      all-gathered once a step at the door, in the compute dtype the
      caller cast it to, and a head that is replicated anyway (ZeRO 0-2)
      is not touched. Its cotangent accumulates locally over the chunks,
      in that dtype, and leaves through ONE all-reduce a step (the
      ``shard_map``'s transpose of an unsharded operand), of which ZeRO
      keeps its slice. Left to the SPMD partitioner a ZeRO-3 head is
      gathered inside the scan body, once a chunk forward and once a chunk
      backward (gpt2-xl over data=4: 186 gathers of 160 MB a step), and a
      head pinned replicated ahead of the scan has its WHOLE gradient
      all-reduced once a chunk. The (B, T) losses come out sharded by
      rows, so the sum and the mask's count below are one all-reduce of a
      scalar each, the partitioner's.
    * a mesh that shards the sequence ('seq'), the vocabulary ('tensor')
      or the layers ('pipe'), a batch the batch axes do not divide, or a
      region that is already manual: the first form, placed by the
      partitioner. No benchmark cell runs one.
    """
    mesh, axes = _batch_shard_axes(x.shape[0])
    if mesh is None:
        nll = _chunked_nll(x, head, targets, bias, remat)
    else:
        rows = P(axes)
        nll = jax.shard_map(
            functools.partial(_chunked_nll, remat=remat), mesh=mesh,
            in_specs=(rows, P(), rows, P()), out_specs=rows,
            check_vma=False)(x, head, targets, bias)
    if loss_mask is not None:
        m = loss_mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


def _chunk_len(T, budget):
    """-> (chunk, padded T): the largest divisor of ``T`` at or under
    ``budget`` positions, with ``T`` itself. A length whose divisors are all
    far under the budget (the 8,191 shifted positions of an 8,192-token
    sequence are a prime: its only one is 1, a scan of 8,191 one-row matmuls)
    is padded instead: the fewest chunks the budget admits or up to twice as
    many, each a multiple of 8 positions, whichever pads least."""
    budget = max(1, min(T, budget))
    chunk = next(cc for cc in range(budget, 0, -1) if T % cc == 0)
    if 8 * chunk >= budget:
        return chunk, T
    fewest = -(-T // budget)
    fits = [(-(-T // (8 * n)) * 8, n) for n in range(fewest, 2 * fewest + 1)]
    fits = [(c, n) for c, n in fits if c <= budget] or fits[-1:]
    chunk, n = min(fits, key=lambda cn: (cn[0] * cn[1], cn[1]))
    return chunk, chunk * n


def _chunked_nll(x, head, targets, bias, remat):
    """(B, T) float32 ``logsumexp - target logit`` of the rows handed in,
    the (B, chunk, V) logits of one chunk of positions at a time."""
    B, T, D = x.shape
    vocab = head.shape[1]
    chunk, padded = _chunk_len(T, _CHUNK_ELEMS // max(1, B * vocab))
    if padded != T:     # rows of zeros: their losses are cut off below
        x = jnp.pad(x, ((0, 0), (0, padded - T), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, padded - T)))
    n = padded // chunk
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)                 # (n, B, C, D)
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)              # (n, B, C)

    def chunk_nll(carry, xt):
        xc, tc = xt
        logits = (xc @ head).astype(jnp.float32)                  # (B, C, V)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return carry, lse - tgt

    # remat the chunk: without it, autodiff keeps every chunk's fp32 logits
    # as scan residuals until the backward pass — (B, T, V)·4 bytes ≈ 2.4G at
    # B=12/T=1024/V=50k, sitting at the fwd peak right when the trunk's saved
    # activations also peak (measured: the gpt2-760m bs=16 OOM-by-374M came
    # from exactly this). Recomputing the chunk's logits in bwd costs one
    # extra (B,C,D)@(D,V) matmul per chunk — measured 0.535 -> 0.525 MFU on
    # the 760m headline, so small-model benches opt out via remat=False.
    body = jax.checkpoint(chunk_nll) if remat else chunk_nll
    _, nll = jax.lax.scan(body, 0.0, (xs, ts))                    # (n, B, C)
    return nll.swapaxes(0, 1).reshape(B, padded)[:, :T]
