"""The first token leaves when prefill returns: ``prefill`` chooses it, the
decode chunk is "step, then sample", and the front-end delivers after the
prefill tick. A request emits the tokens it emitted before the order was
turned round (``the_old_order`` below: sample from carried logits, then
step), through ``generate()`` and through the front-end, on the GPT-2 and
the Llama (dense and routed) trunks."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine as ie
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

TICK = 16
PROMPT = 8
LENGTHS = (1, 2, 16, 17, 33)
GREEDY = dict(do_sample=False, temperature=1.0, top_k=0, top_p=1.0)
SAMPLED = dict(do_sample=True, temperature=0.8, top_k=12, top_p=0.95)
SEED = 7

_LLAMA = LlamaConfig(vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
                     n_head=4, n_kv_head=2, intermediate_size=96,
                     dtype=jnp.float32, remat=False,
                     use_flash_attention=False)
TRUNKS = {
    "gpt2": lambda: GPT2Model(GPT2Config(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4)),
    "llama-dense": lambda: LlamaModel(_LLAMA),
    "llama-routed": lambda: LlamaModel(dataclasses.replace(
        _LLAMA, intermediate_size=32, qk_norm=True, n_experts=8,
        n_experts_per_tok=3)),
}


def _prompt():
    return ((np.arange(PROMPT) * 5)[None, :] % 256).astype(np.int32)


@pytest.fixture(scope="module", params=TRUNKS, ids=list(TRUNKS))
def served(request):
    """(engine, front-end) of one trunk, shared by the module's tests: its
    programs compile once."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.serving import ServingFrontEnd

    engine = ie.InferenceEngine(
        TRUNKS[request.param](),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64))
    front = ServingFrontEnd(engine, DeepSpeedConfig(
        {"serving": {"decode_tick_tokens": TICK}}).serving)
    yield engine, front
    front.close()


def the_old_order(engine, prompt, n, do_sample, temperature, top_k, top_p,
                  eos=None, seed=SEED):
    """What a request emitted before this order: the logits are carried,
    every step samples from them FIRST and then runs ``decode_step`` (the
    last one for nothing). Step by step on the host, nothing shared with
    the programs but the model and the sampling head."""
    module, params = engine.module, engine.params
    eos = -1 if eos is None else eos
    cache = module.init_cache(1, 64)
    logits, cache = jax.jit(module.prefill)(params, jnp.asarray(prompt), cache)
    step = jax.jit(module.decode_step)
    rng, done, out = jax.random.PRNGKey(seed), jnp.zeros((1,), jnp.bool_), []
    for _ in range(n):
        rng, sub = jax.random.split(rng)
        nxt = ie._sample(logits, sub, temperature, top_k, top_p) if do_sample \
            else jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, jnp.int32(max(eos, 0)), nxt)
        done = done | (nxt == eos)
        out.append(int(nxt[0]))
        logits, cache = step(params, nxt, cache)
    return out


@pytest.fixture(scope="module")
def before(served):
    """The old order's longest run for each sampling: a shorter request's
    tokens are its first ones (one split a token, in order)."""
    engine, _ = served
    return {name: the_old_order(engine, _prompt(), max(LENGTHS), **how)
            for name, how in (("greedy", GREEDY), ("sampled", SAMPLED))}


def _serve(front, n, how, stream=None, **kw):
    req = front.submit(_prompt(), max_new_tokens=n, stream=stream, seed=SEED,
                       **how, **kw)
    return req.result(timeout=600)


def _spans_of(req):
    from deepspeed_tpu import telemetry

    return [s for s in telemetry.get_tracer().snapshot()
            if s.args.get("request") == req.id]


@pytest.mark.serving
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
def test_served_is_generated_is_what_it_was(served, before, sampling, n):
    engine, front = served
    how = GREEDY if sampling == "greedy" else SAMPLED
    calls = []
    req = _serve(front, n, how, stream=calls.append)
    assert req.status == "completed" and req.reason == ""
    generated = np.asarray(engine.generate(
        _prompt(), max_new_tokens=n, seed=SEED, **how))
    assert generated.shape == (1, PROMPT + n)
    assert (generated[0, :PROMPT] == _prompt()[0]).all()
    assert req.tokens == generated[0, PROMPT:].tolist() == before[sampling][:n]
    # the first callback is the first token alone; then ticks, the last cut
    sizes = [len(c) for c in calls]
    assert [t for c in calls for t in c] == req.tokens
    rest = n - 1
    assert sizes == [1] + [TICK] * (rest // TICK) + [rest % TICK] * bool(rest % TICK)
    (span,) = [s for s in _spans_of(req) if s.name == "request"]
    assert span.args["decode_ticks"] == req.decode_ticks == math.ceil((n - 1) / TICK)
    assert span.args["new_tokens"] == n


@pytest.mark.serving
@pytest.mark.parametrize("n", [2, 17])
def test_the_first_callback_precedes_the_first_decode_tick(served, n):
    import time

    _, front = served
    stamps = []
    req = _serve(front, n, GREEDY,
                 stream=lambda toks: stamps.append((time.monotonic(), len(toks))))
    spans = _spans_of(req)
    first_decode = min(s.t0 for s in spans if s.name == "decode")
    (prefill,) = [s for s in spans if s.name == "prefill"]
    assert stamps[0][1] == 1
    assert prefill.t1 < req.first_tokens_at <= stamps[0][0] < first_decode
    assert req.prefill_done_at == prefill.t1
    assert req.ttft_s == req.first_tokens_at - req.submitted_at
    # the tick's context is what the cache holds when it starts
    decodes = sorted((s for s in spans if s.name == "decode"),
                     key=lambda s: s.t0)
    assert [s.args["context"] for s in decodes] == \
        [PROMPT + TICK * i for i in range(len(decodes))]
    assert [s.args["index"] for s in decodes] == list(range(len(decodes)))


@pytest.mark.serving
def test_one_token_runs_no_decode_tick(served):
    engine, front = served
    req = _serve(front, 1, GREEDY)
    assert req.status == "completed" and len(req.tokens) == 1
    names = [s.name for s in _spans_of(req)]
    assert "decode" not in names and names.count("deliver") == 1
    assert req.decode_ticks == 0


@pytest.mark.serving
@pytest.mark.parametrize("n", [1, 5, 20])
def test_eos_as_the_first_token_pads_as_generate_does(served, before, n):
    """The greedy first token named as the EOS: the prefill tick finishes
    the request, the rest is EOS, and no decode tick runs."""
    engine, front = served
    eos = before["greedy"][0]
    calls = []
    req = _serve(front, n, GREEDY, stream=calls.append, eos_token_id=eos)
    assert req.status == "completed"
    assert req.tokens == [eos] * n
    generated = np.asarray(engine.generate(
        _prompt(), max_new_tokens=n, eos_token_id=eos, **GREEDY))
    assert req.tokens == generated[0, PROMPT:].tolist()
    assert req.tokens == the_old_order(engine, _prompt(), n, eos=eos, **GREEDY)
    assert calls[0] == [eos] and sum(map(len, calls)) == n
    assert req.decode_ticks == 0
    assert "decode" not in [s.name for s in _spans_of(req)]


@pytest.mark.serving
@pytest.mark.parametrize("n", [17, 33])
def test_an_eos_in_a_decode_tick_ends_the_request_there(served, before, n):
    """An EOS sampled in the first decode tick: served == generated ==
    before, padded to the length asked for, in one decode tick."""
    engine, front = served
    toks = before["sampled"]
    # the first token of the first tick that no earlier token equals: up
    # to it the request's tokens are what they are without an EOS
    at = next(i for i in range(1, TICK) if toks[i] not in toks[:i])
    eos = toks[at]
    req = _serve(front, n, SAMPLED, eos_token_id=eos)
    generated = np.asarray(engine.generate(
        _prompt(), max_new_tokens=n, eos_token_id=eos, seed=SEED, **SAMPLED))
    assert req.tokens == generated[0, PROMPT:].tolist()
    assert req.tokens == the_old_order(engine, _prompt(), n, eos=eos, **SAMPLED)
    assert req.tokens == toks[:at] + [eos] * (n - at)
    assert req.decode_ticks == 1


@pytest.mark.serving
@pytest.mark.chaos
@pytest.mark.parametrize("dies", ["deadline", "failure", "hang"])
def test_a_first_decode_tick_that_dies_leaves_one_token(served, before, dies):
    """The prefill's token is with the caller already: whatever stops the
    first decode tick, the request resolves ``partial`` with that token."""
    from deepspeed_tpu.resilience import chaos

    _, front = served
    # the hook's call #1 is the prefill tick, #2 the first decode tick
    inj, kw, reason = {
        "deadline": (chaos.ChaosInjector(delay_at={"decode_step": [2]},
                                         max_delay_s=3.0),
                     {"deadline_s": 1.5}, "deadline"),
        "failure": (chaos.ChaosInjector(fail_at={"decode_step": [2]}),
                    {}, "error: ChaosError"),
        "hang": (chaos.ChaosInjector(hang_at={"decode_step": [2]}, hang_s=2.0),
                 {}, "timeout"),
    }[dies]
    _serve(front, 20, GREEDY)       # both decode specializations are warm
    cfg = front.cfg
    if dies == "hang":
        front.cfg = cfg.model_copy(update={"decode_tick_timeout_s": 0.5})
    chaos.install_chaos(inj)
    try:
        calls = []
        req = _serve(front, 20, GREEDY, stream=calls.append, **kw)
    finally:
        chaos.uninstall_chaos()
        front.cfg = cfg
    assert req.status == "partial" and req.reason.startswith(reason)
    assert req.tokens == before["greedy"][:1] and calls == [req.tokens]
    assert req.decode_ticks == 0 and req.ttft_s is not None
    (span,) = [s for s in _spans_of(req) if s.name == "request"]
    assert span.args["status"] == "partial" and span.args["new_tokens"] == 1


@pytest.mark.serving
@pytest.mark.chaos
def test_a_prefill_tick_that_dies_delivers_nothing(served):
    from deepspeed_tpu.resilience import chaos

    _, front = served
    chaos.install_chaos(chaos.ChaosInjector(fail_at={"decode_step": [1]}))
    try:
        calls = []
        req = _serve(front, 4, GREEDY, stream=calls.append)
    finally:
        chaos.uninstall_chaos()
    assert req.status == "failed" and not req.tokens and not calls
    assert req.first_tokens_at is None and req.ttft_s is None
    (span,) = [s for s in _spans_of(req) if s.name == "request"]
    assert span.args["first_tokens_at"] is None
    assert span.args["decode_ticks"] == 0


# ------------------------------------------------------------ the programs
@pytest.fixture
def tensor_mesh():
    """The cache's specs name 'tensor': a mesh of one device that has it."""
    with jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tensor",)):
        yield


@pytest.mark.parametrize("which", ["generate", "serving"])
def test_no_logits_cross_a_program_boundary(tensor_mesh, which):
    """prefill hands over a token, the decode program starts from one: no
    ``(B, vocab)`` float leaf among either program's inputs or outputs."""
    model = TRUNKS["gpt2"]()
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, PROMPT), jnp.int32)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    if which == "generate":
        prefill, decode = ie.build_generate_parts(
            model, 5, eos_token_id=None, **GREEDY)
    else:
        prefill, decode = ie.build_serving_programs(
            model, 64, TICK, eos_token_id=None, **GREEDY)
    tok, cache, done, key = jax.eval_shape(prefill, params, ids, rng)
    assert (tok.shape, tok.dtype) == ((2,), jnp.int32)
    assert (done.shape, done.dtype) == ((2,), jnp.bool_)
    assert key.shape == rng.shape
    if which == "generate":
        out = jax.eval_shape(decode, params, ids, tok, cache, done, key)
        assert out.shape == (2, PROMPT + 5)
        outs = [out]
    else:
        outs = jax.eval_shape(decode, params, tok, cache, done, key)
        assert outs[-1].shape == (2, TICK) and outs[0].shape == (2,)
        assert jax.tree.structure(outs[1]) == jax.tree.structure(cache)
    for leaf in jax.tree.leaves((tok, done, key, outs)):
        assert not (leaf.shape[-1:] == (256,)
                    and jnp.issubdtype(leaf.dtype, jnp.floating)), leaf


@pytest.mark.parametrize("n", [1, 2, 9])
def test_generate_runs_one_decode_step_fewer_than_tokens(tensor_mesh, n):
    """``max_new_tokens - 1`` scan steps: no step's logits go unsampled.
    Counted where the model is stepped."""
    model = TRUNKS["gpt2"]()
    steps = []
    real = model.decode_step

    def counted(params, tok, cache):
        steps.append(1)
        return real(params, tok, cache)

    model.decode_step = counted
    jaxpr = jax.make_jaxpr(ie.build_generate_fn(
        model, n, eos_token_id=None, **GREEDY))(
            jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((1, PROMPT), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == n - 1]
    # traced once as the scan's body, over a scan of n - 1 iterations
    assert len(steps) == 1 and len(scans) == 1


def test_generate_refuses_zero_new_tokens():
    with pytest.raises(ValueError, match="first token"):
        ie.build_generate_parts(TRUNKS["gpt2"](), 0, eos_token_id=None,
                                **GREEDY)
