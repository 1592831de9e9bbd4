"""Weight quantization tests — reference csrc/quantization + GroupQuantizer
(module_inject/replace_module.py:143) role: int8/int4 per-group weights,
dequant-on-the-fly serving within tolerance of bf16, memory halved."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, synthetic_lm_batch
from deepspeed_tpu.ops.quantizer import (Quantizer, dequantize_params,
                                         dequantize_tensor, is_quantized_leaf,
                                         quantize_params, quantize_tensor,
                                         quantized_nbytes)

TINY = GPT2Config(vocab_size=512, n_positions=64, n_embd=64, n_layer=2, n_head=4,
                  dtype=jnp.float32, remat=False, use_flash_attention=False)


class TestQuantizeTensor:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_roundtrip_error_bound(self, bits):
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(128, 64).astype(np.float32))
        leaf = quantize_tensor(w, num_bits=bits, group_size=64)
        back = dequantize_tensor(leaf)
        assert back.shape == w.shape and back.dtype == w.dtype
        err = float(jnp.max(jnp.abs(back - w)))
        # symmetric rounding: max error = scale/2 per group
        bound = 0.5 * float(jnp.max(leaf.scale)) * 1.01
        assert err <= bound, (err, bound)

    def test_asymmetric_beats_symmetric_on_shifted_data(self):
        rng = np.random.RandomState(1)
        w = jnp.asarray((rng.rand(128, 32) + 3.0).astype(np.float32))  # all ~[3,4]
        sym = dequantize_tensor(quantize_tensor(w, 8, 64, symmetric=True))
        asym = dequantize_tensor(quantize_tensor(w, 8, 64, symmetric=False))
        assert float(jnp.mean(jnp.abs(asym - w))) < float(jnp.mean(jnp.abs(sym - w)))

    def test_int4_packs_half_bytes(self):
        w = jnp.ones((64, 16), jnp.float32)
        leaf = quantize_tensor(w, num_bits=4, group_size=32)
        assert leaf.q.shape == (2, 16, 16)  # group dim halved by packing

    def test_quantizer_op_surface(self):
        q = Quantizer(q_groups=4, num_bits=8)
        w = jnp.asarray(np.random.RandomState(2).randn(64, 32).astype(np.float32))
        back = q.dequantize(q.quantize(w))
        assert float(jnp.max(jnp.abs(back - w))) < 0.05

    def test_quantizer_1d_buffer(self):
        q = Quantizer(q_groups=4, num_bits=8)
        w = jnp.asarray(np.random.RandomState(3).randn(256).astype(np.float32))
        back = q.dequantize(q.quantize(w))
        assert back.shape == w.shape
        assert float(jnp.max(jnp.abs(back - w))) < 0.05


class TestQuantizeParams:
    def test_tree_transform_and_memory(self):
        model = GPT2Model(TINY)
        params = model.init_params(jax.random.PRNGKey(0))
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        before = sum(x.nbytes for x in jax.tree.leaves(params))
        qp = quantize_params(params, num_bits=8, min_numel=1024)
        leaves = jax.tree.leaves(qp, is_leaf=is_quantized_leaf)
        assert any(is_quantized_leaf(l) for l in leaves)
        # embeddings (incl. tied head) / ln / bias excluded
        assert not is_quantized_leaf(qp["wte"])
        assert not is_quantized_leaf(qp["wpe"])
        assert not is_quantized_leaf(qp["blocks"]["ln1_g"])
        assert is_quantized_leaf(qp["blocks"]["qkv_w"])
        after = quantized_nbytes(qp)
        # tiny model: embeddings are a big share and stay bf16; projection
        # weights (the quantized part) halve
        assert after < 0.75 * before, (before, after)
        back = dequantize_params(qp, jnp.bfloat16)
        assert back["blocks"]["qkv_w"].shape == params["blocks"]["qkv_w"].shape
        assert back["blocks"]["qkv_w"].dtype == jnp.bfloat16


class TestInt8Serving:
    def test_int8_generate_close_to_bf16(self):
        comm.cdb = None
        model = GPT2Model(TINY)
        params = model.init_params(jax.random.PRNGKey(0))
        ids = np.asarray(synthetic_lm_batch(2, 12, TINY.vocab_size)["input_ids"])

        ref_engine = deepspeed_tpu.init_inference(
            model, config={"dtype": "fp32", "max_out_tokens": 64}, params=params)
        ref_logits = np.asarray(ref_engine.forward(ids))
        ref_out = np.asarray(ref_engine.generate(ids, max_new_tokens=8))

        comm.cdb = None
        q_engine = deepspeed_tpu.init_inference(
            model, config={"dtype": "int8", "max_out_tokens": 64,
                           "quant": {"weight": {"quantized_initialization":
                                                {"min_numel": 1024}}}},
            params=params)
        q_logits = np.asarray(q_engine.forward(ids))
        q_out = np.asarray(q_engine.generate(ids, max_new_tokens=8))

        # projection weights halve vs bf16 serving; embeddings stay bf16
        from deepspeed_tpu.ops.quantizer import quantized_nbytes
        bf16_equiv = sum(int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(params))
        assert quantized_nbytes(q_engine.params) < 0.75 * bf16_equiv
        # logits close; generation shape identical and prompts preserved
        rel = np.abs(q_logits - ref_logits).max() / (np.abs(ref_logits).max() + 1e-9)
        assert rel < 0.15, rel
        assert q_out.shape == ref_out.shape
        assert (q_out[:, :12] == ids).all()


# ---------------------------------------------------------------------------
# ops/quantizer.py — padded-group accounting (the satellite fix, pinned)
# ---------------------------------------------------------------------------
class TestQuantizerPadding:
    def test_group_layout_pads_instead_of_collapsing(self):
        from deepspeed_tpu.ops.quantizer import quant_group_layout

        assert quant_group_layout(100, 64) == (64, 2, 128)
        assert quant_group_layout(128, 64) == (64, 2, 128)
        assert quant_group_layout(37, 16) == (16, 3, 48)
        # group >= dim: one whole-dim group, nothing padded
        assert quant_group_layout(48, 64) == (48, 1, 48)
        assert quant_group_layout(48, 0) == (48, 1, 48)

    def test_nbytes_bills_padded_wire_bytes(self):
        """static_comm_bytes bills what actually crosses the wire: the
        PADDED codes (+ scales), not the logical element count."""
        from deepspeed_tpu.ops.quantizer import quantize_tensor

        w = jnp.asarray(np.random.RandomState(0).randn(100, 8),
                        jnp.float32)
        qt = quantize_tensor(w, num_bits=8, group_size=64)
        assert qt.q.shape == (2, 64, 8)          # 2 groups of 64, padded
        assert qt.scale.shape == (2, 8)
        assert qt.nbytes == 2 * 64 * 8 + 2 * 8 * 4
        assert qt.nbytes > 100 * 8               # > logical int8 bytes

    @pytest.mark.parametrize("shape,gs", [((100, 8), 64), ((37,), 16),
                                          ((3, 100, 8), 32)])
    def test_roundtrip_exact_shape_and_bounded_error(self, shape, gs):
        from deepspeed_tpu.ops.quantizer import (dequantize_tensor,
                                                 quantize_tensor)

        w = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
        qt = quantize_tensor(w, num_bits=8, group_size=gs)
        back = dequantize_tensor(qt)
        assert back.shape == w.shape
        # per-group symmetric int8: |err| <= group absmax / 127 / 2 + round
        bound = float(jnp.max(jnp.abs(w))) / 127.0 * 0.51 * 2
        assert float(jnp.max(jnp.abs(back - w))) <= max(bound, 2e-2)

    def test_int4_roundtrip_padded(self):
        from deepspeed_tpu.ops.quantizer import (dequantize_tensor,
                                                 quantize_tensor)

        w = jnp.asarray(np.random.RandomState(2).randn(100, 4), jnp.float32)
        qt = quantize_tensor(w, num_bits=4, group_size=64)
        assert qt.q.shape == (2, 32, 4)          # nibble-packed, padded
        back = dequantize_tensor(qt)
        assert back.shape == w.shape
        assert float(jnp.max(jnp.abs(back - w))) <= \
            float(jnp.max(jnp.abs(w))) / 7.0 * 0.51 * 2 + 1e-3
