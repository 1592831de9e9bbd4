"""Inference engine: jitted KV-cache generation with tensor parallelism.

Counterpart of the reference's ``deepspeed/inference/engine.py``
(InferenceEngine :89: _create_model_parallel_group :259,
_apply_injection_policy :413, _create_cuda_graph :531, forward :591,
_generate :619). TPU-native:

* the whole decode loop is ONE compiled program (``lax.scan`` over new
  tokens, donated cache) — the role the reference's CUDA-graph capture plays,
  but including the sampling logic;
* tensor parallelism is the mesh's 'tensor' axis: weights get their TP
  PartitionSpecs from the model (or AutoTP, module_inject/auto_tp.py) and XLA
  inserts the per-layer allreduce the reference does in LinearAllreduce
  (module_inject/layers.py:15);
* the KV cache is sharded over heads on the tensor axis.

Model protocol: init_params(rng), init_cache(B, max_len), prefill(params,
ids, cache) → (logits, cache), decode_step(params, token, cache) →
(logits, cache), param_partition_specs(), cache_partition_specs().

A STEP of the decode loop emits ``n >= 1`` tokens a row. The autoregressive
step (``_decode_scan_step``) emits one: ``decode_step`` on the last token,
then the next. A model that generates by diffusion over blocks says so
(``block_decoding``: a ``models/common.py::BlockDecoding``) and has
``block_step(params, tokens, masked, cache, pending=)``; its step
(``_block_scan_step``) emits a BLOCK: up to ``steps`` forward passes over
the same ``length`` positions, each unmasking some of them, and NO pass of
its own to commit the finished block's keys and values: the block is left
PENDING (its final tokens, a leaf of the cache) and its commit rides in
the next block's first pass, which runs over both (``2 x length`` rows, the
weights streamed once). The last block of a request is never committed:
nothing reads its rows. Everything around the
step is one code: the prefill program ends in the FIRST emission (one
token; or the first block, which opens with what the prompt left over of a
block and has nothing pending before it), ``generate()`` and the serving
chunk scan steps and count tokens by what the steps return.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu import telemetry as _telemetry
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.utils.logging import log_dist


def _sample(logits, rng, temperature: float, top_k: int, top_p: float):
    """Sampling head: temperature / top-k / nucleus."""
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def build_generate_fn(module, max_new_tokens: int, do_sample: bool,
                      temperature: float, top_k: int, top_p: float,
                      eos_token_id: Optional[int], param_transform=None,
                      cache_shardings=None):
    """The jittable prefill + scan-decode generation program, shared by
    InferenceEngine.generate and DeepSpeedHybridEngine.generate.
    ``param_transform`` preprocesses the param tree inside the trace (e.g.
    the training engine's host-offload stream-in). Composed from
    ``build_generate_parts`` (ONE source of the generation logic, so the
    fused fast path and the observed split path cannot diverge), with the
    transform hoisted so it runs once in the single program.
    ``cache_shardings`` pins the in-program KV cache to the registry's
    placement (defaults to the module's own cache specs)."""
    prefill, decode = build_generate_parts(
        module, max_new_tokens, do_sample, temperature, top_k, top_p,
        eos_token_id, param_transform=None, cache_shardings=cache_shardings)

    def gen(params, ids, rng):
        if param_transform is not None:
            params = param_transform(params)
        return decode(params, ids, *prefill(params, ids, rng))

    return gen


def _resolve_cache_shardings(module, cache_shardings):
    """THE KV-cache placement resolution, shared by the fused generate,
    the split prefill/decode pair and the serving tick programs: an
    explicit registry-derived ``cache_shardings`` wins, else the module's
    own cache specs. One function so the consumers cannot diverge."""
    if cache_shardings is not None:
        return cache_shardings
    if hasattr(module, "cache_partition_specs"):
        return module.cache_partition_specs()
    return None


def _sampling(do_sample: bool, temperature: float, top_k: int, top_p: float,
              eos_token_id: Optional[int]) -> tuple:
    """How a program chooses tokens, as :func:`_next_token` takes it
    (``eos`` -1 = none: no token equals it)."""
    return (do_sample, temperature, top_k, top_p,
            -1 if eos_token_id is None else int(eos_token_id))


def _next_token(logits, done, rng, do_sample: bool, temperature: float,
                top_k: int, top_p: float, eos: int):
    """Choose each row's next token from ``logits``: the largest, or with
    ``do_sample`` one ``split`` of the carried key and the sampling head;
    rows past their EOS hold the EOS token. What ends the prefill and every
    decode step — a token is chosen by the program that computed its
    logits. Greedy programs carry the key untouched: nothing reads it, and
    lowering a threefry split costs each of them 0.2-0.5 s of set-up on
    the chip's host (PERF.md, PR 30). -> (tok, done, rng)."""
    if do_sample:
        rng, sub = jax.random.split(rng)
        tok = _sample(logits, sub, temperature, top_k, top_p)
    else:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tok = jnp.where(done, jnp.int32(max(eos, 0)), tok)
    return tok, done | (tok == eos), rng


def _decode_scan_step(module, params, sampling):
    """One token of the decode loop as a ``lax.scan`` body over the carry
    ``(tok, cache, done, rng)``: one ``module.decode_step`` on the token the
    carry holds, then :func:`_next_token` from the new logits, emitted.
    Step, then sample: what passes from program to program is a TOKEN,
    never the ``(B, vocab)`` logits, and no step's logits go unsampled. The
    SINGLE source of the per-token logic, shared by the fused/observed
    generate paths and the serving front-end's chunked decode
    (serving/frontend.py) — the three consumers cannot diverge
    numerically. ``sampling``: :func:`_sampling`'s."""

    def step(carry, _):
        tok, cache, done, rng = carry
        logits, cache = module.decode_step(params, tok, cache)
        tok, done, rng = _next_token(logits, done, rng, *sampling)
        return (tok, cache, done, rng), tok

    return step


def step_tokens(module) -> int:
    """Tokens a row one step of ``module``'s decode loop emits: 1, or the
    block length of a model that generates by diffusion over blocks."""
    dec = getattr(module, "block_decoding", None)
    return 1 if dec is None else int(dec.length)


def _transfer_counts(dec) -> tuple:
    """Positions each of a block's denoising passes unmasks at least:
    ``length // steps``, one more in the first ``length % steps``."""
    base, more = divmod(dec.length, dec.steps)
    return tuple(base + (s < more) for s in range(dec.steps))


def block_passes(dec, given: int = 0) -> int:
    """Denoising passes a block needs when every pass unmasks exactly its
    count (the two static rules), ``given`` of its positions not masked."""
    left, passes = dec.length - given, 0
    for n in _transfer_counts(dec):
        if left <= 0:
            break
        left, passes = left - n, passes + 1
    return passes


def _unmask(conf, masked, n, dec):
    """Which of a block's ``masked`` (B, Lb) positions a pass unmasks, by
    ``dec.remasking`` from the confidences ``conf`` (B, Lb) of the tokens
    chosen there and the pass's count ``n`` (traced): ``n`` of them (all
    that are left if fewer), ties to the left; the dynamic rule every one
    above the threshold where those are at least ``n``. Never a position
    that is not masked."""
    if dec.remasking == "sequential":
        return masked & (jnp.cumsum(masked, axis=1) <= n)
    conf = jnp.where(masked, conf, -jnp.inf)
    # a position's rank by confidence: those above it, and its equals to
    # the left
    at = jnp.arange(conf.shape[1])
    above = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (at[None, :] < at[:, None]))
    top = masked & (jnp.sum(above, axis=2) < n)
    if dec.remasking == "low_confidence_static":
        return top
    high = masked & (conf > dec.threshold)
    return jnp.where(jnp.sum(high, axis=1, keepdims=True) >= n, high, top)


def _denoise(module, params, tokens, masked, cache, rng, sampling, dec,
             given: int, carrying: bool):
    """A block's denoising passes: each ``module.block_step`` over the block
    as it stands; a token is chosen at every position (the largest logit, or
    ``do_sample`` the sampling head) with its confidence (the softmax's
    probability of it) and :func:`_unmask` moves some in, until no mask is
    left: the static rules run ``block_passes`` passes, a fixed trip; the
    dynamic one while a mask is left. ``carrying``: the FIRST pass carries
    the cache's pending block (the one finished before this: its rows are
    committed by the pass that starts the next, ``block_step(pending=)``),
    so a step whose passes are more than one holds the model twice, the
    first pass and the loop's. No pass commits THIS block: nothing a pass
    writes of it outlives the next pass over it, which writes the same
    slots. -> (tokens, cache, rng)."""
    from deepspeed_tpu.telemetry.scopes import scope

    do_sample, temperature, top_k, top_p, _ = sampling
    counts = jnp.asarray(_transfer_counts(dec), jnp.int32)

    def one_pass(s, tokens, masked, cache, rng, pending=None):
        logits, cache = module.block_step(params, tokens, masked, cache,
                                          pending=pending)
        with scope("head/unmask"):
            if do_sample:
                rng, sub = jax.random.split(rng)
                x0 = _sample(logits, sub, temperature, top_k, top_p)
            else:
                x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            conf = jnp.exp(
                jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
                - jax.scipy.special.logsumexp(logits, axis=-1))
            move = _unmask(conf, masked, counts[s], dec)
            return jnp.where(move, x0, tokens), masked & ~move, cache, rng

    state, start, n = (tokens, masked, cache, rng), 0, block_passes(dec, given)
    if carrying:
        state, start = one_pass(0, *state, pending=cache["pending"]), 1
    if dec.remasking == "low_confidence_dynamic":
        _, *state = jax.lax.while_loop(
            lambda c: jnp.any(c[2]), lambda c: (c[0] + 1, *one_pass(*c)),
            (jnp.int32(start), *state))
    elif start < n:
        state = jax.lax.fori_loop(start, n, lambda s, c: one_pass(s, *c),
                                  state)
    tokens, _, cache, rng = state
    return tokens, cache, rng


def _block_scan_step(module, params, sampling, dec):
    """One BLOCK of the decode loop, over the same carry ``(tok, cache,
    done, rng)`` as :func:`_decode_scan_step`: the block all masked, but for
    the ``opens`` (B, given) tokens it is handed (what a prompt left over of
    a block: the prefill program's step alone, the FIRST block of a request,
    before which nothing is pending), denoised (:func:`_denoise`) and left
    in the cache as the PENDING block, for the next step's first pass to
    commit. Emits the block's NEW tokens (B, length - given); a row holds
    its EOS token from the first one chosen on (``done``), as the
    autoregressive step's rows do. ``tok``, the last token emitted, is
    carried for the carry's sake: a block step reads nothing of it."""
    from deepspeed_tpu.models.common import BLOCK_COUNTS

    eos = sampling[-1]

    def step(carry, opens=None):
        _, cache, done, rng = carry
        B = done.shape[0]
        given = 0 if opens is None else opens.shape[1]
        tokens = jnp.zeros((B, dec.length), jnp.int32)
        if given:
            tokens = tokens.at[:, :given].set(opens.astype(jnp.int32))
        masked = jnp.broadcast_to(jnp.arange(dec.length) >= given,
                                  tokens.shape)
        tokens, cache, rng = _denoise(
            module, params, tokens, masked, cache, rng, sampling, dec, given,
            carrying=opens is None)
        cache = {**cache, "pending": tokens,
                 "block_passes": cache["block_passes"].at[
                     BLOCK_COUNTS.index("blocks")].add(1)}
        new = tokens[:, given:]
        ended = done[:, None] | (jnp.cumsum(new == eos, axis=1)
                                 - (new == eos) > 0)
        new = jnp.where(ended, jnp.int32(max(eos, 0)), new)
        done = done | jnp.any(new == eos, axis=1)
        return (new[:, -1], cache, done, rng), new

    return step


def _scan_step(module, params, sampling):
    """The decode loop's scan body over ``(tok, cache, done, rng)``: the
    model's kind of step (it emits :func:`step_tokens` tokens a row)."""
    dec = getattr(module, "block_decoding", None)
    if dec is None:
        return _decode_scan_step(module, params, sampling)
    return _block_scan_step(module, params, sampling, dec)


def _prefill_program(module, cache_len, sampling, param_transform,
                     cache_shardings):
    """``prefill(params, ids, rng) -> (tok, cache, done, rng)`` over a cache
    of ``cache_len(T)`` slots: the prompt's pass and the FIRST token
    (:func:`_next_token`, as every decode step), so whoever runs it holds a
    token when it returns. ``sampling``: :func:`_sampling`'s.

    For a model that generates by diffusion over blocks the prefill chooses
    nothing: ``prefill(params, ids, rng) -> (tok, cache, done, rng, first)``
    runs the prompt's WHOLE blocks through ``module.prefill`` (under the
    model's block-causal mask), then the first block step, which opens with
    the ``T % length`` tokens the prompt left over; ``first`` (B, length - T
    % length) are the first tokens that exist."""
    dec = getattr(module, "block_decoding", None)

    def prefill(params, ids, rng):
        if param_transform is not None:
            params = param_transform(params)
        B, T = ids.shape
        cache = module.init_cache(B, cache_len(T))
        cc = _resolve_cache_shardings(module, cache_shardings)
        if cc is not None:
            cache = jax.lax.with_sharding_constraint(cache, cc)
        if dec is not None:
            whole = T - T % dec.length
            if whole:
                _, cache = module.prefill(params, ids[:, :whole], cache)
            carry, first = _block_scan_step(module, params, sampling, dec)(
                (None, cache, jnp.zeros((B,), jnp.bool_), rng),
                ids[:, whole:])
            return (*carry, first)
        logits, cache = module.prefill(params, ids, cache)
        tok, done, rng = _next_token(logits, jnp.zeros((B,), jnp.bool_), rng,
                                     *sampling)
        return tok, cache, done, rng

    return prefill


def _blocks_after_first(dec, prompt_len: int, max_new_tokens: int) -> int:
    """Block steps ``max_new_tokens`` need after the prefill program's."""
    owed = max_new_tokens - (dec.length - prompt_len % dec.length)
    return max(0, -(-owed // dec.length))


def build_generate_parts(module, max_new_tokens: int, do_sample: bool,
                         temperature: float, top_k: int, top_p: float,
                         eos_token_id: Optional[int], param_transform=None,
                         cache_shardings=None):
    """Generation split at the prefill/decode boundary so the host can
    observe TTFT (time to first token) and the decode tail separately —
    the two numbers that define serving latency. Used directly when
    telemetry or ``profile_model_time`` is active; ``build_generate_fn``
    composes the same two pieces into the fused single-program fast path.
    ``prefill(params, ids, rng) -> (tok, cache, done, rng)`` hands over the
    first token; ``decode(params, ids, tok, cache, done, rng)`` scans the
    ``max_new_tokens - 1`` steps the other tokens need and returns the ids
    with all of them appended. ``param_transform`` (dequant / offload
    stream-in) runs inside each program, so numerics match the fused path
    exactly. A model that generates by diffusion over blocks
    (``block_decoding``): the prefill hands over its first BLOCK as a fifth
    value, ``decode`` takes it as a seventh argument, scans the block steps
    the other tokens need and cuts the last block to ``max_new_tokens``."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens {max_new_tokens}: the prefill "
                         "already chooses the first token")
    sampling = _sampling(do_sample, temperature, top_k, top_p, eos_token_id)
    dec = getattr(module, "block_decoding", None)

    def decode(params, ids, tok, cache, done, rng, first=None):
        if param_transform is not None:
            params = param_transform(params)
        step = _scan_step(module, params, sampling)
        if dec is not None:
            T = ids.shape[1]
            _, toks = jax.lax.scan(
                step, (tok, cache, done, rng), None,
                length=_blocks_after_first(dec, T, max_new_tokens))
            # (steps, B, length) -> (B, steps x length)
            rest = toks.transpose(1, 0, 2).reshape(ids.shape[0], -1)
            return jnp.concatenate(
                [ids, first.astype(ids.dtype), rest.astype(ids.dtype)],
                axis=1)[:, :T + max_new_tokens]
        _, toks = jax.lax.scan(
            step, (tok, cache, done, rng), None, length=max_new_tokens - 1)
        return jnp.concatenate(
            [ids, tok[:, None].astype(ids.dtype), toks.T.astype(ids.dtype)],
            axis=1)

    def cache_len(T):
        if dec is None:
            return T + max_new_tokens
        return T - T % dec.length + dec.length * (
            1 + _blocks_after_first(dec, T, max_new_tokens))

    return _prefill_program(module, cache_len, sampling, param_transform,
                            cache_shardings), decode


def build_serving_programs(module, max_total_len: int, chunk_tokens: int,
                           do_sample: bool, temperature: float, top_k: int,
                           top_p: float, eos_token_id: Optional[int],
                           param_transform=None, cache_shardings=None):
    """``(prefill, decode_chunk)`` for the serving front-end's tick loop
    (serving/frontend.py). ``prefill(params, ids, rng) -> (tok, cache, done,
    rng)`` sizes the cache once at ``max_total_len`` and returns the FIRST
    token, so the front-end delivers it when the prefill tick returns;
    ``decode_chunk(params, tok, cache, done, rng) -> (tok, cache, done, rng,
    toks)`` advances ``chunk_tokens`` steps from the last token and returns
    the full carry, with ``toks`` (B, chunk) the chunk's NEW tokens, so the
    HOST can check deadlines / cancellation / drain between chunks — the
    price of interruptibility is one dispatch gap per chunk instead of one
    per request. Both end in :func:`_next_token`, in ``generate()``'s order
    of key splits (one for the first token, then one a step), so a request
    served through the front-end emits exactly the tokens ``generate()``
    would. A model whose step emits a BLOCK of ``n`` tokens
    (:func:`step_tokens`): ``chunk_tokens`` is a whole number of blocks, a
    chunk scans ``chunk_tokens // n`` block steps, and the prefill returns a
    fifth value, the tokens of the first block (what the front-end
    delivers when the prefill tick returns: the first tokens that exist)."""
    sampling = _sampling(do_sample, temperature, top_k, top_p, eos_token_id)
    n = step_tokens(module)
    if chunk_tokens % n:
        raise ValueError(
            f"decode_tick_tokens {chunk_tokens}: a whole number of the "
            f"{n}-token blocks a step of this model emits")

    def decode_chunk(params, tok, cache, done, rng):
        if param_transform is not None:
            params = param_transform(params)
        (tok, cache, done, rng), toks = jax.lax.scan(
            _scan_step(module, params, sampling), (tok, cache, done, rng),
            None, length=chunk_tokens // n)
        # (B, chunk) int32 — rows past their EOS hold the EOS token, same
        # post-EOS convention as generate()
        if n > 1:               # (steps, B, n) -> (B, steps x n)
            return tok, cache, done, rng, toks.transpose(1, 0, 2).reshape(
                toks.shape[1], -1)
        return tok, cache, done, rng, toks.T

    # the last block of the longest request ends inside the allocation
    return _prefill_program(module, lambda T: -(-max_total_len // n) * n,
                            sampling, param_transform,
                            cache_shardings), decode_chunk


def _served_as_given(params, shardings, dtype) -> bool:
    """True where every leaf of ``params`` is a device array that the cast
    of its floating leaves to ``dtype`` and the placement onto ``shardings``
    would leave as it is."""
    def same(x, sh):
        return isinstance(x, jax.Array) and not x.is_deleted() \
            and (x.dtype == dtype or not jnp.issubdtype(x.dtype, jnp.floating)) \
            and x.sharding.is_equivalent_to(sh, x.ndim)

    try:
        return all(jax.tree.leaves(jax.tree.map(same, params, shardings)))
    except ValueError:          # another tree than the specs describe
        return False


class InferenceEngine:
    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 params: Any = None, mesh=None):
        self._config = config or DeepSpeedInferenceConfig()
        self.module = model
        self.dtype = self._config.jnp_dtype()
        # dtype int8 = weight-only quantized serving (reference engine.py
        # quantization path + GroupQuantizer): weights stored int8/int4,
        # compute stays bf16 — dequant fuses into the compiled forward
        self._quantize_weights = self.dtype == jnp.int8
        if self._quantize_weights:
            self.dtype = jnp.bfloat16

        tp = self._config.tp_size
        # expert-parallel serving (reference inference/config.py:167 moe
        # block + containers/base_moe.py): the expert axis carries the gated
        # a2a dispatch inside the compiled prefill/decode programs
        ep = int(self._config.moe.ep_size) if self._config.moe.enabled else 1
        if mesh is None:
            mesh = dist.get_mesh() if dist.is_initialized() else None
            # the installed (training) mesh serves only while it agrees with
            # what this config asks for; an explicit tp_size / ep_size it
            # does not carry gets the mesh it names, not a warning
            if mesh is None \
                    or (tp != 1 and mesh.shape.get("tensor", 1) != tp) \
                    or (ep != 1 and mesh.shape.get("expert", 1) != ep):
                n = jax.device_count()
                if n % (tp * ep):
                    raise ValueError(f"tp_size {tp} x moe.ep_size {ep} does "
                                     f"not divide device count {n}")
                from deepspeed_tpu.sharding import ensure_global_mesh

                mesh = ensure_global_mesh(
                    axis_dims={"pipe": 1, "data": n // (tp * ep),
                               "expert": ep, "seq": 1, "tensor": tp})
                dist.init_distributed(mesh=mesh, verbose=False)
        self.mesh = mesh
        self.mp_world_size = mesh.shape.get("tensor", 1)
        self.ep_world_size = mesh.shape.get("expert", 1)

        # ---- parameters: shard per TP specs (the injection/AutoTP step) ----
        specs = None
        if hasattr(model, "param_partition_specs"):
            specs = model.param_partition_specs()
        shapes = (jax.eval_shape(lambda: params) if params is not None
                  else jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
        if specs is None or self._config.injection_policy is not None:
            from deepspeed_tpu.module_inject.auto_tp import AutoTP

            # a policy refines the model's own specs where given; only without
            # model specs does AutoTP name-pattern inference take over fully
            specs = AutoTP.infer_specs(shapes, policy=self._config.injection_policy,
                                       base_specs=specs)

        to_dtype = lambda x: x.astype(self.dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
        from deepspeed_tpu.sharding import (INHERIT, ShardingRegistry,
                                            sharded_jit)

        # the spec registry — the ONE source the serving front-end, the
        # split prefill/decode pair and the fused generate read placements
        # from (params here; the KV cache lazily via cache_shardings)
        self.sharding = ShardingRegistry(mesh)
        specs = self.sharding.fit(specs, shapes)
        self.sharding.register("params", specs)
        shardings = self.sharding.shardings("params")
        with mesh:
            if params is not None and _served_as_given(params, shardings,
                                                       self.dtype):
                # already in the served type and placement: the engine holds
                # the caller's buffers, not a second copy of the weights (a
                # 13.84 GB model on a 16 GB chip has room for one)
                self.params = params
            elif params is not None:
                self.params = sharded_jit(
                    lambda p: jax.tree.map(to_dtype, p),
                    label="inference/cast_params", donate_argnums=(),
                    mesh=mesh, in_shardings=INHERIT,
                    out_shardings=shardings)(params)
            elif self._config.checkpoint:
                # serve a TRAINING checkpoint at any tp: orbax restores the
                # params subtree straight into the serving shardings (the
                # reference's sharded-checkpoint loading / mp-reshard,
                # inference/engine.py:336-506)
                from deepspeed_tpu.runtime.checkpoint_engine.engine import \
                    load_inference_params

                abstract = jax.tree.map(
                    lambda x, s: jax.ShapeDtypeStruct(
                        x.shape,
                        self.dtype if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
                        sharding=s),
                    shapes, shardings)
                self.params = load_inference_params(
                    self._config.checkpoint, abstract,
                    tag=self._config.checkpoint_config.get("tag"))
            else:
                self.params = sharded_jit(
                    lambda: jax.tree.map(to_dtype, model.init_params(jax.random.PRNGKey(0))),
                    label="inference/init_params", donate_argnums=(),
                    mesh=mesh, in_shardings=(),
                    out_shardings=shardings)()
        self._param_specs = specs
        self._dequant = None
        if self._quantize_weights:
            from deepspeed_tpu.ops.quantizer import (dequantize_params,
                                                     quantize_params,
                                                     quantized_nbytes)

            wq = self._config.quant.weight
            if not (self._config.quant.enabled and wq.enabled):
                log_dist("dtype int8 but quant.weight disabled: serving bf16 "
                         "weights unquantized", ranks=[0])
            else:
                bits = wq.num_bits if wq.num_bits in (4, 8) else 8
                if bits != wq.num_bits:
                    from deepspeed_tpu.utils.logging import logger

                    logger.warning(f"quant.weight.num_bits={wq.num_bits} "
                                   f"unsupported; using {bits}")
                before = sum(x.nbytes for x in jax.tree.leaves(self.params))
                with mesh:
                    self.params = quantize_params(
                        self.params, num_bits=bits,
                        symmetric=(wq.q_type != "asymmetric"),
                        q_groups=wq.q_groups if wq.q_groups > 1 else None,
                        min_numel=int(wq.quantized_initialization.get(
                            "min_numel", 1 << 16)))
                dtype = self.dtype
                self._dequant = lambda p: dequantize_params(p, dtype)
                log_dist(f"weight quantization: {before/1e6:.1f}MB -> "
                         f"{quantized_nbytes(self.params)/1e6:.1f}MB "
                         f"(int{bits})", ranks=[0])
        self._compiled = {}
        self._model_profile_enabled = False
        self._model_times = []
        ep_tag = f", ep={self.ep_world_size}" if self.ep_world_size > 1 else ""
        log_dist(f"InferenceEngine ready: dtype={jnp.dtype(self.dtype).name}, "
                 f"tp={self.mp_world_size}{ep_tag}", ranks=[0])

    def _params_in_shardings(self):
        """Registry param shardings, or explicit INHERIT for the quantized
        tree (its structure no longer matches the spec tree)."""
        from deepspeed_tpu.sharding import INHERIT

        if self._dequant is not None:
            return INHERIT
        return self.sharding.shardings("params")

    # ----------------------------------------------------------------- forward
    def forward(self, input_ids, *args, **kwargs):
        """HF-style forward. Extra positional arrays pass through to the
        module's apply — the diffusers surface (UNet takes (sample,
        timestep, encoder_hidden_states), reference
        model_implementations/diffusers/unet.py wrapper role)."""
        key = ("fwd", len(args))
        if key not in self._compiled:
            from deepspeed_tpu.sharding import INHERIT, sharded_jit

            dq = self._dequant or (lambda p: p)
            # inputs are arbitrary client arrays (diffusion latents, ids of
            # any batch size) — explicitly INHERIT their placement; params
            # are pinned to the registry's specs (unless weight-quantized:
            # the quantized tree's structure differs from the spec tree, so
            # its committed placement is inherited instead)
            self._compiled[key] = sharded_jit(
                lambda p, *xs: self.module.apply(dq(p), *xs),
                label=f"inference/forward[args={len(args)}]",
                donate_argnums=(), mesh=self.mesh,
                in_shardings=(self._params_in_shardings(),)
                + (INHERIT,) * (len(args) + 1),
                out_shardings=INHERIT)

        def to_dev(a):
            # jax arrays (the natural denoising-loop state) pass through
            # without a host round-trip; only foreign tensor types (torch)
            # detour via numpy
            try:
                return jnp.asarray(a)
            except TypeError:
                return jnp.asarray(np.asarray(a))

        xs = [to_dev(a) for a in (input_ids, *args)]
        t0 = time.perf_counter()
        with self.mesh:
            out = self._compiled[key](self.params, *xs)
        if self._model_profile_enabled:
            jax.block_until_ready(out)
            self._model_times.append(time.perf_counter() - t0)
        return out

    __call__ = forward

    # ---------------------------------------------------------------- generate
    def generate(self, input_ids, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0, **kwargs):
        """Autoregressive generation, fully jitted (prefill + scan decode).

        Mirrors the reference's _generate (:619) surface for the common kwargs.
        Returns (B, T_prompt + max_new_tokens) token ids (post-EOS positions
        hold the EOS token).
        """
        ids = jnp.asarray(np.asarray(input_ids))
        B, T = ids.shape
        max_len = T + max_new_tokens
        if max_len > self._config.max_out_tokens:
            raise ValueError(f"sequence {max_len} exceeds max_out_tokens "
                             f"{self._config.max_out_tokens} (reference engine raises too)")
        rng = jax.random.PRNGKey(seed)
        session = _telemetry.get_session()
        observed = self._model_profile_enabled or (
            session is not None and session.cfg.inference)
        if not observed:
            # fast path: ONE compiled program (prefill + scan decode), no
            # host round-trip between first token and decode
            # B and T are NOT in the key: jit re-specializes per input shape,
            # and gen derives them from ids inside the trace. The ids spec IS
            # keyed: a dp-divisible and a non-divisible batch compile with
            # different (explicit) in/out placements.
            from deepspeed_tpu.sharding import sharded_jit

            ids_sh = self.sharding.ids_sharding(batch_size=B)
            key = ("gen", max_new_tokens, do_sample, temperature, top_k,
                   top_p, eos_token_id, ids_sh.spec)
            if key not in self._compiled:
                repl = self.sharding.replicated()
                self._compiled[key] = sharded_jit(
                    build_generate_fn(
                        self.module, max_new_tokens, do_sample, temperature,
                        top_k, top_p, eos_token_id,
                        param_transform=self._dequant,
                        cache_shardings=self.sharding.cache_shardings(self.module)),
                    label=f"inference/generate[new={max_new_tokens}]",
                    donate_argnums=(), mesh=self.mesh,
                    in_shardings=(self._params_in_shardings(), ids_sh, repl),
                    out_shardings=ids_sh,
                    meta={"params_argnum": 0})
            with self.mesh:
                ids = jax.device_put(ids, ids_sh)
                return self._compiled[key](self.params, ids, rng)
        return self._generate_observed(ids, rng, max_new_tokens, do_sample,
                                       temperature, top_k, top_p, eos_token_id)

    def _generate_observed(self, ids, rng, max_new_tokens, do_sample,
                           temperature, top_k, top_p, eos_token_id):
        """Two-program generation (prefill | scan decode) with a host sync at
        the boundary: TTFT and per-token decode latency become observable.
        The extra sync costs one dispatch gap per request — the price of
        measuring, only paid when telemetry or profile_model_time asks."""
        from deepspeed_tpu.sharding import INHERIT, sharded_jit

        ids_sh = self.sharding.ids_sharding(batch_size=int(ids.shape[0]))
        key = ("gen2", max_new_tokens, do_sample, temperature, top_k, top_p,
               eos_token_id, ids_sh.spec)
        if key not in self._compiled:
            cache_sh = self.sharding.cache_shardings(self.module)
            pf, df = build_generate_parts(
                self.module, max_new_tokens, do_sample, temperature, top_k,
                top_p, eos_token_id, param_transform=self._dequant,
                cache_shardings=cache_sh)
            params_in = self._params_in_shardings()
            cache_io = cache_sh if cache_sh is not None else INHERIT
            repl = self.sharding.replicated()
            # a block-diffusion model's prefill hands over its first block too
            first = (INHERIT,) * (step_tokens(self.module) > 1)
            self._compiled[key] = (
                sharded_jit(pf, label=f"inference/prefill[new={max_new_tokens}]",
                            donate_argnums=(), mesh=self.mesh,
                            in_shardings=(params_in, ids_sh, repl),
                            out_shardings=(INHERIT, cache_io, INHERIT, repl)
                            + first,
                            meta={"params_argnum": 0}),
                sharded_jit(df, label=f"inference/decode[new={max_new_tokens}]",
                            # the cache is dead after the decode consumes it —
                            # donating it avoids a second live KV buffer
                            donate_argnums=(3,), mesh=self.mesh,
                            in_shardings=(params_in, ids_sh, INHERIT,
                                          cache_io, INHERIT, repl) + first,
                            out_shardings=ids_sh,
                            meta={"params_argnum": 0, "cache_argnum": 3}))
        pf, df = self._compiled[key]
        ids = jax.device_put(ids, ids_sh)
        tracer = _telemetry.get_tracer()
        t0 = time.perf_counter()
        with self.mesh:
            with tracer.span("prefill", cat="inference", tokens=int(ids.shape[1])):
                carried = pf(self.params, ids, rng)
                jax.block_until_ready(carried[0])
            ttft = time.perf_counter() - t0
            t1 = time.perf_counter()
            # the first token came with the prefill: the scan makes the rest
            with tracer.span("decode", cat="inference",
                             tokens=int(max_new_tokens) - 1):
                out = df(self.params, ids, *carried)
                jax.block_until_ready(out)
            decode_s = time.perf_counter() - t1
        total = time.perf_counter() - t0
        reg = _telemetry.get_registry()
        if reg.enabled:
            B = int(ids.shape[0])
            reg.counter("inference/requests").inc(B)
            reg.counter("inference/generated_tokens").inc(B * int(max_new_tokens))
            reg.histogram("inference/ttft_seconds").observe(ttft)
            reg.histogram("inference/decode_per_token_seconds").observe(
                decode_s / max(1, int(max_new_tokens) - 1))
            reg.histogram("inference/request_seconds").observe(total)
        if self._model_profile_enabled:
            self._model_times.append(total)
        return out

    # -------------------------------------------------------------- DS parity
    def _create_model_parallel_group(self):
        return dist.new_group(("tensor",))

    def profile_model_time(self, use_cuda_events: bool = False):
        """Record per-request model time (reference engine.py:277 stores
        ``_model_times`` for ``model_times()``). ``use_cuda_events`` is
        accepted for parity; on TPU the sync is ``block_until_ready``.
        Also switches generate() onto the split prefill/decode path, so
        TTFT/decode show up in telemetry when a session is active."""
        self._model_profile_enabled = True
        self._model_times = []

    def model_times(self):
        """Drain and return the list of per-request model times (seconds)."""
        assert self._model_profile_enabled, \
            "model_times() requires profile_model_time() first (reference contract)"
        times = self._model_times
        self._model_times = []
        return times

    @property
    def mp_group(self):
        return dist.new_group(("tensor",)) if dist.is_initialized() else None
