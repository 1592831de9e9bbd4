"""The system under test, built through the entry points a user calls.

A train system is ``deepspeed_tpu.initialize`` -> ``engine.train_batch``;
a serve system is ``init_inference`` -> ``serving.from_ds_config`` ->
``ServingFrontEnd.submit``. Both are the same for every model family: the
configuration's ``family`` names the module under ``benchmark/families/``
that builds the program's model and holds the plain reference each system
checks itself against once, at set-up. Only this module and a family's
``build_model`` import ``deepspeed_tpu``. Weights are random, made on the
device from ``--seed``.
"""

import functools

import numpy as np

from benchmark import families

# |loss(system) - loss(reference)| on one seeded 128-token sequence. The
# system computes in bf16 (8 mantissa bits) through 24-48 layers; the
# reference in float32 at "highest". At loss ~11 PERF.md records what the
# chip showed; float16-or-lower accumulation or a dropped term moves the
# loss by far more than this.
TRAIN_LOSS_TOL = 0.01
# Every token the server chose must have a REFERENCE logit within this
# margin of the reference's best at that position (teacher-forced). With
# random weights the logits' spread is ~0.8, neighbours at the top are
# ~0.01-0.1 apart, and bf16 through 48 layers moves a logit by a few
# hundredths: tokens flip on rounding, logits do not move by 0.1.
SERVE_LOGIT_MARGIN = 0.1
CHECK_PROMPT, CHECK_NEW, CHECK_SEQ = 64, 32, 128


class TrainSystem:
    kind = "train"

    def __init__(self, cfg, traffic, seed, chips):
        import deepspeed_tpu

        eng = traffic["engine"]
        self.cfg, self.family = cfg, families.get(cfg["family"])
        self.vocab = self.family.vocab_size(cfg)
        model = self.family.build_model(cfg, "train")
        ds = dict(cfg["train"]["ds_config"])
        ds.update({
            "train_micro_batch_size_per_gpu": eng["micro_batch_per_chip"],
            "gradient_accumulation_steps": eng["gradient_accumulation_steps"],
            "zero_optimization": {"stage": eng["zero_stage"]},
            "tpu": {"data": chips}, "steps_per_print": 0,
            # FIXED, not --seed: engine/init_state closes over its PRNG key,
            # so every new seed is a new program and a 15 s compile that a
            # seed seen before does not pay (set-up 17 s or 32 s by history).
            # The weights are drawn from --seed below, key as an ARGUMENT.
            "seed": 0})
        self.engine, *_ = deepspeed_tpu.initialize(model=model, config=ds)
        if self.engine.mesh.size != chips:
            raise SystemExit(f"benchmark: train mesh has "
                             f"{self.engine.mesh.size} devices, cell asks {chips}")
        self.global_batch = self.engine.train_batch_size()
        self._draw_weights(model, int(seed))

    def _draw_weights(self, model, seed):
        """Weights from ``--seed`` in one jitted call, straight into the
        engine's own placements and dtypes. The fixed-seed buffers are freed
        first, so nothing is held twice and the peak stays the run's own.
        AdamW's moments are zeros whatever the seed and stay."""
        import jax

        eng = self.engine
        state, sh = eng.state, eng.state_shardings
        p_dtypes, m_dtypes = jax.tree.map(lambda x: x.dtype,
                                          (state.params, state.master))

        def draw(key):
            raw = model.init_params(key)
            cast = lambda new, dtype: new.astype(dtype)
            return (jax.tree.map(cast, raw, p_dtypes),
                    None if m_dtypes is None
                    else jax.tree.map(cast, raw, m_dtypes))

        for leaf in jax.tree.leaves((state.params, state.master)):
            leaf.delete()
        with eng.mesh:
            params, master = jax.jit(
                draw, out_shardings=(sh.params, sh.master))(
                    jax.random.PRNGKey(seed))
        eng.state = state._replace(params=params, master=master)

    def step(self, batch):
        """One optimizer step on a host batch; the host read ends it."""
        return float(self.engine.train_batch({"input_ids": batch}))

    def instrument(self, rec):
        pass        # the driver's own spans are the train cells' spans

    def check(self, seed):
        """The engine's own model function and parameters against the
        reference on one seeded sequence. -> (ok, detail)."""
        import jax

        ids = np.random.default_rng([int(seed), 13]).integers(
            0, self.vocab, size=CHECK_SEQ, dtype=np.int32)
        params, module = self.engine.state.params, self.engine.module
        with self.engine.mesh:
            got = float(jax.jit(lambda p, b: module.loss(
                p, {"input_ids": b}))(params, ids[None]))
            want = float(jax.jit(functools.partial(
                self.family.reference_loss, cfg=self.cfg))(params, ids))
        ok = bool(np.isfinite(got)) and abs(got - want) <= TRAIN_LOSS_TOL
        return ok, {"loss_system": got, "loss_reference": want,
                    "tolerance": TRAIN_LOSS_TOL}

    def close(self):
        self.engine = None


class ServeSystem:
    kind = "serve"

    def __init__(self, cfg, traffic, seed, chips):
        import jax
        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu import serving
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        self.cfg, self.family = cfg, families.get(cfg["family"])
        self.vocab = self.family.vocab_size(cfg)
        model = self.family.build_model(cfg, "serve")
        serve = cfg["serve"]
        # the weights, in the type they are served in, in one jitted call
        params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), model.init_params(key)))(
                jax.random.PRNGKey(int(seed)))
        self.engine = deepspeed_tpu.init_inference(
            model, dtype=serve["dtype"], params=params,
            max_out_tokens=serve["max_out_tokens"],
            tensor_parallel={"tp_size": int(
                traffic.get("engine", {}).get("tp_size", 1))})
        del params
        self.front = serving.from_ds_config(
            self.engine, DeepSpeedConfig({"serving": serve["serving"]}))
        # tokens a decode tick delivers: the server's, not a copy of it
        self.tick_tokens = int(self.front.cfg.decode_tick_tokens)
        self._warm_rng = np.random.default_rng(0)

    def submit(self, prompt, new_tokens, stream):
        return self.front.submit(prompt, max_new_tokens=int(new_tokens),
                                 stream=stream)

    def warm(self, prompt_len, new_tokens):
        req = self.submit(self._warm_rng.integers(
            0, self.vocab, size=prompt_len, dtype=np.int32), new_tokens, None)
        req.result(timeout=1200.0)
        if req.status != "completed":
            raise SystemExit(f"benchmark: warm-up request (prompt "
                             f"{prompt_len}) ended {req.status!r} {req.reason!r}")

    def instrument(self, rec):
        """Host spans around the front-end's two inner calls, from here:
        ``request`` (``_process``: one request in service) and ``tick``
        (``_tick``: one blocked device call). Only the traced run does
        this; the end-to-end run leaves the server untouched."""
        front = self.front
        tick, process = front._tick, front._process

        def timed_tick(req, fn, warm_key):
            with rec.span("tick", phase=str(warm_key[0]),
                          context=int(req.prompt.shape[1]) + len(req.tokens)):
                return tick(req, fn, warm_key=warm_key)

        def timed_process(req):
            with rec.span("request"):
                return process(req)

        front._tick, front._process = timed_tick, timed_process

    def check(self, seed):
        """One request through the server, teacher-forced through the
        reference: how far below the reference's best logit each chosen
        token's reference logit lies. -> (ok, detail)."""
        import jax

        ids = np.random.default_rng([int(seed), 17]).integers(
            0, self.vocab, size=CHECK_PROMPT, dtype=np.int32)
        req = self.submit(ids, CHECK_NEW, None)
        req.result(timeout=1200.0)
        if req.status != "completed" or len(req.tokens) != CHECK_NEW:
            return False, {"status": req.status, "reason": req.reason}
        toks = np.asarray(req.tokens, dtype=np.int32)
        full = np.concatenate([ids, toks])
        with self.engine.mesh:
            lg = np.asarray(jax.jit(functools.partial(
                self.family.reference_logits, cfg=self.cfg))(
                    self.engine.params, full))
        rows = lg[CHECK_PROMPT - 1:CHECK_PROMPT - 1 + CHECK_NEW]
        short = rows.max(axis=-1) - rows[np.arange(CHECK_NEW), toks]
        worst = float(short.max())
        return worst <= SERVE_LOGIT_MARGIN, {
            "worst_logit_shortfall": worst, "margin": SERVE_LOGIT_MARGIN,
            "tokens_equal_to_reference_argmax":
                int((rows.argmax(axis=-1) == toks).sum()),
            "tokens": CHECK_NEW}

    def close(self):
        self.front.begin_drain("shutdown")
        self.front.drain(timeout=60.0)
        if self.front.state != "dead":
            raise SystemExit("benchmark: the serving worker did not stop")


def build(kind, cfg, traffic, seed, chips):
    return {"train": TrainSystem, "serve": ServeSystem}[kind](
        cfg, traffic, seed, chips)
