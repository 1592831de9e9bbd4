"""Cross-family composition: the subsystems must work on every model family,
not just the GPT-2 they were built against — hybrid RLHF on LLaMA, int8
serving on LLaMA (GQA tree), checkpoint reshard on BERT, AutoTP raw-tree
classification for the NeoX/GPT-J layouts."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.llama import PRESETS as LLAMA_PRESETS, LlamaModel


def _tiny_llama(**over):
    return LlamaModel(dataclasses.replace(
        LLAMA_PRESETS["llama-tiny"], use_flash_attention=False, **over))


def test_hybrid_engine_rlhf_on_llama():
    """Train↔generate flips over shared live params with a GQA/RoPE model."""
    model = _tiny_llama()
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1},
                "hybrid_engine": {"enabled": True, "max_out_tokens": 64},
                "steps_per_print": 0})
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 512, size=(8, 8)).astype(np.int32)
    seq = np.asarray(engine.generate(prompts, max_new_tokens=4))
    assert seq.shape == (8, 12)
    batch = {"input_ids": seq.astype(np.int32)}
    l0 = float(engine.train_batch(batch))
    for _ in range(3):
        ln = float(engine.train_batch(batch))
    assert ln < l0
    seq2 = np.asarray(engine.generate(prompts, max_new_tokens=4))
    assert seq2.shape == (8, 12)          # generates from the UPDATED params


def test_int8_serving_on_llama_gqa_tree():
    """Weight-only int8 quantized serving must handle the GQA param tree
    (unequal q/k/v widths) within quantization tolerance of bf16."""
    model = _tiny_llama(dtype=jnp.float32, remat=False)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = np.random.RandomState(1).randint(0, 512, size=(2, 12)).astype(np.int32)

    ref_eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "fp32", "max_out_tokens": 64}, params=params)
    ref = np.asarray(ref_eng.forward(ids))

    from deepspeed_tpu.comm import comm

    comm.cdb = None
    q_eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "int8", "max_out_tokens": 64,
                       "quant": {"enabled": True,
                                 "weight": {"enabled": True, "num_bits": 8,
                                            "q_groups": 4,
                                            "quantized_initialization":
                                                {"min_numel": 16}}}},
        params=params)
    out = np.asarray(q_eng.forward(ids))
    # int8 per-group quantization: logits track within a few percent of range
    scale = np.abs(ref).max()
    assert np.abs(out - ref).max() / scale < 0.06, \
        np.abs(out - ref).max() / scale


def test_checkpoint_reshard_on_bert():
    """Universal-checkpoint role exercised with the encoder family: save at
    zero-2/dp=8, reload at zero-1/tp=2 — reshard must be silent and exact."""
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models.bert import PRESETS, BertModel, synthetic_mlm_batch
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.checkpoint_engine.engine import wait_for_pending_saves

    import tempfile

    cfg = dataclasses.replace(PRESETS["bert-tiny"], use_flash_attention=False)
    batch = synthetic_mlm_batch(8, 32, cfg.vocab_size)
    with tempfile.TemporaryDirectory() as tmp:
        comm.cdb = None
        mesh = build_mesh(axis_dims={"pipe": 1, "data": 8, "expert": 1,
                                     "seq": 1, "tensor": 1})
        comm.init_distributed(mesh=mesh, verbose=False)
        e1, *_ = deepspeed_tpu.initialize(
            model=BertModel(cfg),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 2}, "steps_per_print": 0})
        for _ in range(3):
            e1.train_batch(batch)
        e1.save_checkpoint(tmp)
        wait_for_pending_saves()
        w = np.asarray(e1.state.params["blocks"]["qkv_w"])

        comm.cdb = None
        mesh2 = build_mesh(axis_dims={"pipe": 1, "data": 4, "expert": 1,
                                      "seq": 1, "tensor": 2})
        comm.init_distributed(mesh=mesh2, verbose=False)
        e2, *_ = deepspeed_tpu.initialize(
            model=BertModel(cfg),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 1}, "steps_per_print": 0})
        e2.load_checkpoint(tmp)
        assert e2.global_steps == 3
        np.testing.assert_array_equal(
            np.asarray(e2.state.params["blocks"]["qkv_w"]), w)
        assert np.isfinite(float(e2.train_batch(batch)))


def test_autotp_classifies_neox_and_gptj_trees():
    """AutoTP name patterns must classify the NeoX and GPT-J raw state-dict
    layouts (reference containers gptneox.py / gptj.py name sets)."""
    from deepspeed_tpu.module_inject.auto_tp import AutoTP
    from deepspeed_tpu.module_inject.hf import state_dict_to_tree

    d, ffn = 16, 64
    sd = {}
    # NeoX names
    sd["gpt_neox.layers.0.attention.query_key_value.weight"] = np.zeros((3 * d, d), np.float32)
    sd["gpt_neox.layers.0.attention.dense.weight"] = np.zeros((d, d), np.float32)
    sd["gpt_neox.layers.0.mlp.dense_h_to_4h.weight"] = np.zeros((ffn, d), np.float32)
    sd["gpt_neox.layers.0.mlp.dense_4h_to_h.weight"] = np.zeros((d, ffn), np.float32)
    sd["embed_out.weight"] = np.zeros((256, d), np.float32)
    # GPT-J names
    sd["transformer.h.0.attn.q_proj.weight"] = np.zeros((d, d), np.float32)
    sd["transformer.h.0.attn.out_proj.weight"] = np.zeros((d, d), np.float32)
    sd["transformer.h.0.mlp.fc_in.weight"] = np.zeros((ffn, d), np.float32)
    sd["transformer.h.0.mlp.fc_out.weight"] = np.zeros((d, ffn), np.float32)
    tree = state_dict_to_tree(sd)
    specs = AutoTP.infer_specs(jax.eval_shape(lambda: tree))
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: hasattr(x, "index"))[0]}
    get = lambda frag: next(v for k, v in flat.items() if frag in k)
    assert tuple(get("query_key_value")) == (None, "tensor")
    assert tuple(get("attention/dense")) == ("tensor", None)
    assert tuple(get("dense_h_to_4h")) == (None, "tensor")
    assert tuple(get("dense_4h_to_h")) == ("tensor", None)
    assert tuple(get("embed_out")) == (None, "tensor")
    assert tuple(get("q_proj")) == (None, "tensor")
    assert tuple(get("out_proj")) == ("tensor", None)
    assert tuple(get("fc_in")) == (None, "tensor")
    assert tuple(get("fc_out")) == ("tensor", None)


def test_autotp_classifies_raw_bert_tree():
    """A raw BERT state-dict tree: paths are '/'-joined, so the
    intermediate.dense / output.dense patterns must use [./] separators
    (reference container bert.py name set)."""
    from deepspeed_tpu.module_inject.auto_tp import AutoTP
    from deepspeed_tpu.module_inject.hf import state_dict_to_tree

    d, ffn = 16, 64
    sd = {}
    pre = "bert.encoder.layer.0"
    sd[f"{pre}.attention.self.query.weight"] = np.zeros((d, d), np.float32)
    sd[f"{pre}.attention.self.key.weight"] = np.zeros((d, d), np.float32)
    sd[f"{pre}.attention.self.value.weight"] = np.zeros((d, d), np.float32)
    sd[f"{pre}.attention.output.dense.weight"] = np.zeros((d, d), np.float32)
    sd[f"{pre}.intermediate.dense.weight"] = np.zeros((ffn, d), np.float32)
    sd[f"{pre}.output.dense.weight"] = np.zeros((d, ffn), np.float32)
    tree = state_dict_to_tree(sd)
    specs = AutoTP.infer_specs(jax.eval_shape(lambda: tree))
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: hasattr(x, "index"))[0]}
    get = lambda frag: next(v for k, v in flat.items() if frag in k)
    assert tuple(get("self/query")) == (None, "tensor")
    assert tuple(get("attention/output/dense")) == ("tensor", None)
    assert tuple(get("intermediate/dense")) == (None, "tensor")
    # MLP output projection (NOT the attention one) must be row-parallel
    mlp_out = next(v for k, v in flat.items()
                   if "output/dense" in k and "attention" not in k)
    assert tuple(mlp_out) == ("tensor", None)


@pytest.mark.parametrize("name,n_head,head_dim", [
    ("gpt2-760m", 16, 96),      # GPT-3 Table 2.1 "Large"
    ("gpt2-xl", 25, 64),        # GPT-2 1.5B
    ("gpt2-1.3b", 16, 128),     # GPT-3 Table 2.1 "XL" (the repo's 16-head cut)
    ("bert-large", 16, 64),     # BERT-large
])
def test_preset_keeps_its_published_heads(name, n_head, head_dim, monkeypatch):
    """A preset is the published architecture on every backend: what
    resolve_family hands back, and the model built from it, carry the
    published head count and width — nothing swaps them on a TPU."""
    from deepspeed_tpu.models.registry import resolve_family

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model_cls, _, presets = resolve_family(name)
    cfg = presets[name]
    assert (cfg.n_head, cfg.head_dim) == (n_head, head_dim)
    assert cfg.n_head * cfg.head_dim == cfg.n_embd
    built = model_cls(cfg).config
    assert (built.n_head, built.head_dim) == (n_head, head_dim)


def test_llama32_1b_preset_matches_hf_shape():
    """llama3.2-1b: ~1.24B params, GQA 32h/8kv, llama3 NTK rope scaling —
    the shape of HF meta-llama/Llama-3.2-1B."""
    from deepspeed_tpu.models.llama import PRESETS

    c = PRESETS["llama3.2-1b"]
    n = c.num_params()
    assert abs(n - 1.236e9) / 1.236e9 < 0.02, n
    assert c.n_head == 32 and c.n_kv_head == 8 and c.tie_embeddings
    assert c.rope_scaling["rope_type"] == "llama3"
    assert c.rope_scaling["factor"] == 32.0
