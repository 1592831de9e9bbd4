"""Weight quantization ops — int8/int4 per-group symmetric/asymmetric.

TPU-native counterpart of the reference's quantization kernels
(``csrc/quantization/pt_binding.cpp`` quantize/dequantize ops,
``deepspeed/ops/quantizer``) and the ``GroupQuantizer`` used by module
injection (``module_inject/replace_module.py:143``): weights are stored as
int8 (or nibble-packed int4) with one scale (and zero-point, asymmetric
mode) per group, and dequantized ON THE FLY inside the compiled forward —
XLA fuses the convert+scale into the matmul's operand read, so serving
memory (and HBM bandwidth, the decode bottleneck) is halved/quartered while
the MXU still computes in bf16.

Group layout: groups tile the LAST-BUT-ONE (contraction) dim of an
``(..., in, out)`` weight — each group of ``group_size`` input rows shares a
scale per output column, matching the reference's group-count semantics
(``q_groups``). 1-D and small tensors are left unquantized (their bytes are
noise; the reference likewise only quantizes the big projection weights).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """A quantized weight leaf: int8/packed-int4 codes + per-group scales.

    Registered as a pytree node so quantized param trees pass through jit /
    device_put / shardings transparently; the static metadata (bit width,
    original shape/dtype) rides in the treedef, not as traced values.
    """

    def __init__(self, num_bits, q, scale, zero, shape, dtype):
        self.num_bits = int(num_bits)
        self.q = q
        self.scale = scale
        self.zero = zero              # None in symmetric mode
        self.shape = tuple(shape)
        self.dtype = str(dtype)

    def tree_flatten(self):
        return (self.q, self.scale, self.zero), (self.num_bits, self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale, zero = children
        num_bits, shape, dtype = aux
        return cls(num_bits, q, scale, zero, shape, dtype)

    @property
    def nbytes(self) -> int:
        n = self.q.size + self.scale.size * 4
        if self.zero is not None:
            n += self.zero.size * 4
        return n

    def __repr__(self):
        return (f"QuantizedTensor(int{self.num_bits}, shape={self.shape}, "
                f"dtype={self.dtype})")


def quant_group_layout(n_in: int, group_size: int):
    """(group_size, n_groups, padded_in) for an ``n_in``-row contraction dim.

    A group size the dim does not divide PADS the dim up to the next group
    boundary instead of silently collapsing to one whole-dim group (the
    old behavior): the padded rows are what actually crosses the wire in a
    quantized gather, so ``QuantizedTensor.nbytes`` — the number
    ``static_comm_bytes`` bills — must account them (pinned by
    tests/unit/test_quantization.py). ``group_size`` ≥ the dim still means one
    group (nothing to pad against)."""
    if group_size <= 0 or group_size >= n_in:
        return n_in, 1, n_in
    padded = ((n_in + group_size - 1) // group_size) * group_size
    return group_size, padded // group_size, padded


def _group_reshape(w, group_size: int):
    """(..., in, out) → (..., n_groups, group_size, out), zero-padding the
    ``in`` dim up to a group boundary when needed (see
    :func:`quant_group_layout`)."""
    *lead, n_in, n_out = w.shape
    group_size, _, padded = quant_group_layout(n_in, group_size)
    if padded != n_in:
        w = jnp.pad(w, [(0, 0)] * len(lead) + [(0, padded - n_in), (0, 0)])
    return w.reshape(*lead, padded // group_size, group_size, n_out), group_size


def quantize_tensor(w, num_bits: int = 8, group_size: int = 128,
                    symmetric: bool = True):
    """Quantize one (..., in, out) float tensor → quantized-leaf dict.

    int8: values in [-127, 127]. int4: values in [-7, 7], two nibbles packed
    per int8 byte along the group axis (group_size must then be even).
    Asymmetric mode stores a per-group zero-point instead of centering at 0.
    """
    assert num_bits in (8, 4), num_bits
    if num_bits == 4 and group_size % 2:
        # nibble packing pairs rows within a group: round an odd group up
        # (the pre-padding code collapsed such sizes to one whole-dim
        # group; with padded groups the even neighbor keeps them working)
        group_size += 1
    orig_dtype = w.dtype
    orig_shape = tuple(int(s) for s in w.shape)
    if w.ndim == 1:
        # flat buffers (reference ds_quantizer quantizes 1-D gradients too):
        # treat as a single-column matrix, group along the length
        w = w.reshape(-1, 1)
    g, group_size = _group_reshape(w.astype(jnp.float32), group_size)
    qmax = 127.0 if num_bits == 8 else 7.0
    if symmetric:
        absmax = jnp.max(jnp.abs(g), axis=-2, keepdims=True)      # (..., G, 1, out)
        scale = absmax / qmax
        zero = None
        q = jnp.round(g / jnp.maximum(scale, 1e-12))
    else:
        lo = jnp.min(g, axis=-2, keepdims=True)
        hi = jnp.max(g, axis=-2, keepdims=True)
        scale = (hi - lo) / (2 * qmax)
        zero = (hi + lo) / 2
        q = jnp.round((g - zero) / jnp.maximum(scale, 1e-12))
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    if num_bits == 4:
        assert q.shape[-2] % 2 == 0, "int4 needs even group_size"
        lo4 = q[..., 0::2, :]
        hi4 = q[..., 1::2, :]
        q = ((hi4.astype(jnp.uint8) << 4) |
             (lo4.astype(jnp.uint8) & 0x0F)).astype(jnp.int8)
    return QuantizedTensor(
        num_bits, q, scale.squeeze(-2).astype(jnp.float32),
        zero.squeeze(-2).astype(jnp.float32) if zero is not None else None,
        orig_shape, jnp.dtype(orig_dtype))


def dequantize_tensor(leaf: "QuantizedTensor", dtype=None):
    """QuantizedTensor → dense tensor (jit-traceable)."""
    q = leaf.q
    scale = leaf.scale[..., None, :]                     # (..., G, 1, out)
    if leaf.num_bits == 4:
        u = q.astype(jnp.uint8)
        lo4 = (u & 0x0F).astype(jnp.int8)
        lo4 = jnp.where(lo4 >= 8, lo4 - 16, lo4)         # sign-extend nibble
        hi4 = (u >> 4).astype(jnp.int8)
        hi4 = jnp.where(hi4 >= 8, hi4 - 16, hi4)
        g = jnp.stack([lo4, hi4], axis=-2)               # (..., gs/2, 2, out)
        q = g.reshape(*q.shape[:-2], q.shape[-2] * 2, q.shape[-1])
    w = q.astype(jnp.float32) * scale
    if leaf.zero is not None:
        w = w + leaf.zero[..., None, :]
    out_dtype = dtype or jnp.dtype(leaf.dtype)
    # collapse (G, gs) back to the (possibly padded) contraction dim, then
    # strip the group padding off before restoring the original shape
    n_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[0]
    w = w.reshape(*w.shape[:-3], w.shape[-3] * w.shape[-2], w.shape[-1])
    if w.shape[-2] != n_in:
        w = jax.lax.slice_in_dim(w, 0, n_in, axis=w.ndim - 2)
    return w.reshape(leaf.shape).astype(out_dtype)


def is_quantized_leaf(x) -> bool:
    return isinstance(x, QuantizedTensor)


def _eligible(path: str, leaf, min_numel: int, exclude) -> bool:
    if not hasattr(leaf, "shape") or len(leaf.shape) < 2:
        return False
    if not jnp.issubdtype(jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype")
                          else leaf.dtype, jnp.floating):
        return False
    if int(np.prod(leaf.shape)) < min_numel:
        return False
    return not any(pat in path for pat in (exclude or ()))


DEFAULT_EXCLUDE = ("wte", "wpe", "embed", "ln", "bias")


def quantize_params(params: Any, num_bits: int = 8, group_size: int = 128,
                    symmetric: bool = True, min_numel: int = 1 << 16,
                    exclude=DEFAULT_EXCLUDE, q_groups: Optional[int] = None) -> Any:
    """Pytree → pytree with big 2-D+ float leaves replaced by quantized-leaf
    dicts. Embeddings (incl. the tied lm head), layernorms, and biases are
    excluded by default — like the reference, only the projection matrices
    are quantized. ``q_groups`` (reference semantics: groups per tensor)
    overrides ``group_size`` per leaf as in_dim // q_groups."""
    from deepspeed_tpu.utils.pytree import path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        p = path_str(path)
        if _eligible(p, leaf, min_numel, exclude):
            gs = group_size if not q_groups else max(1, leaf.shape[-2] // q_groups)
            out.append(quantize_tensor(leaf, num_bits=num_bits,
                                       group_size=gs, symmetric=symmetric))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_params(params: Any, dtype=None) -> Any:
    """Inverse tree transform; safe inside jit (runs per compiled call and
    fuses into consumers)."""
    return jax.tree_util.tree_map(
        lambda x: dequantize_tensor(x, dtype) if is_quantized_leaf(x) else
        (x.astype(dtype) if dtype is not None and hasattr(x, "dtype")
         and jnp.issubdtype(x.dtype, jnp.floating) else x),
        params, is_leaf=is_quantized_leaf)


def quantized_nbytes(params: Any) -> int:
    """Total bytes of a (possibly partially) quantized tree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=is_quantized_leaf):
        if is_quantized_leaf(leaf) or hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total


class Quantizer:
    """Reference ``ds_quantizer`` op surface (ops/quantizer/__init__.py):
    stateful wrapper over the functional ops."""

    def __init__(self, q_groups: int = 1, num_bits: int = 8, symmetric: bool = True):
        self.q_groups = q_groups
        self.num_bits = num_bits
        self.symmetric = symmetric

    def quantize(self, w):
        group_dim = w.shape[-2] if w.ndim >= 2 else w.shape[0]
        group_size = max(1, group_dim // self.q_groups)
        return quantize_tensor(w, num_bits=self.num_bits, group_size=group_size,
                               symmetric=self.symmetric)

    def dequantize(self, leaf, dtype=None):
        return dequantize_tensor(leaf, dtype)
