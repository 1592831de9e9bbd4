"""SDAR-MoE (``model_type: sdar_moe``; the published ``config.json`` of
``JetLM/SDAR-30B-A3B-Chat``): a Qwen3-MoE trunk that GENERATES BY DIFFUSION
OVER BLOCKS. The program's model, the plain reference, and the operations
and bytes the algorithm needs, for ONE CHIP'S SHARE of a stated deployment.

Every function takes the configuration file's dict. The sizes are under its
``"model"`` key, named as in the published file (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size`` = one expert's width, ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob``, ``rope_theta``, ``rms_norm_eps``,
``vocab_size``; ``intermediate_size`` 6144 is the width of a dense MLP that no
layer has: ``decoder_sparse_step`` 1, ``mlp_only_layers`` []). **The share**
(model-configs guide, section 4): where the file lists ``num_experts`` under
``reduced``, the value under ``model`` is the number of experts HELD here,
``published.num_experts`` the router's width and ``share.experts_first`` the
router's number of the first held one. The router scores all of them and
picks ``num_experts_per_tok``; the pairs that fall on held experts are
computed, the others add exact zeros, in the program and in the reference
alike, which reads the same stacked leaves ``(L, held, ...)``. A sliced
vocabulary is a smaller vocabulary. How a block is denoised is under
``serve`` (``block_length``, ``denoising_steps``, ``remasking``,
``confidence_threshold``): ``build_model`` hands it to the program's model.

**The reference** (``reference_forward``): float32, ``highest`` matmul
precision, no kernel, no cache, no batching, one sequence. A layer, x (T,
2048): ``h = x + Attn(RMSNorm(x; g1))``, ``y = h + MoE(RMSNorm(h; g2))``,
eps 1e-6, no bias anywhere.

* Attn: ``q = n W_q`` as heads x 128, ``k = n W_k``, ``v = n W_v`` as KV
  heads x 128; each head of q and of k through an RMSNorm over its 128
  columns with ONE gain vector for q and one for k (Qwen3's ``q_norm`` /
  ``k_norm``); rotate-half rotary embedding on all 128 columns at absolute
  positions, theta 1e6, no scaling; scores ``q_i . k_j / sqrt(128)`` for the
  j the mask admits, softmax, ``o = concat W_o``. The mask is BLOCK-causal:
  j is admitted for i iff ``j // Lb <= i // Lb``, blocks counted from
  position 0 of the sequence.
* MoE, every layer: ``p = softmax(n W_r)`` over the router's whole width,
  the ``num_experts_per_tok`` largest, ``w = p_top / sum(p_top)``, ``sum_e
  w_e W_down,e (silu(n W_gate,e) * n W_up,e)`` over the chosen experts HELD
  here, one at a time; no shared expert.
* Final RMSNorm, untied head. **The logits row of position i predicts the
  token AT i** (a masked position is read as the mask token and predicted
  in place, as LLaDA and BD3-LM do; no shift). ``masked`` (T,) says which
  positions are read as the mask token.

``prefix`` (the witness): the T rows may instead be a BLOCK at absolute
positions ``start ..`` that sees given K/V rows of every earlier position
(a whole sequence's ``(L, S, KV heads, 128)``, cut at ``start`` by the
mask) and itself whole; ``held`` gives a held
expert's side (taken / left out) a (layer, row), as the openPangu family
has it: the reference evaluated on a program's OWN choices.

**Generation** (``reference_generate``; SDAR's published ``generate.py``,
written from its description): a prompt of P tokens, ``Lb`` = block length,
``S`` = denoising steps, ``n_s = Lb // S`` (+1 for the first ``Lb % S``
steps). The ``P // Lb`` whole blocks of the prompt are context; the ``P %
Lb`` left-over tokens open the first generated block, unmasked. For each
block: every position not given is masked. Up to S times, while a mask is
left: one forward pass (here: of the whole sequence so far, no cache); at
each masked position the largest logit's token ``x0`` and its confidence
``c = softmax(logits)[x0]``; unmask by the rule: ``sequential`` (the ``n_s``
leftmost masked), ``low_confidence_static`` (the ``n_s`` masked of largest
c), ``low_confidence_dynamic`` (every masked position with ``c >
confidence_threshold`` if they are at least ``n_s``, else the ``n_s`` of
largest c). Generation stops after ``new_tokens`` (the last block is cut to
length) or at an EOS inside a finished block, from which on the row holds
the EOS token.

Departures from the published code, each on purpose:
(1) masked-ness is a boolean carried beside the tokens, not ``token ==
mask_token_id``: with seeded random weights the model CHOOSES the mask id
once in 18,992 tokens, about a fifth of all runs of the cell;
(2) a pass unmasks masked positions only: where fewer are left than the
step's count (a first block that opens with prompt tokens) it takes those,
never a position that is not masked (the published ``topk`` would);
(3) the program writes a block's K/V into its own slots at every pass and
overwrites them at the commit, which is the same arithmetic as "not
stored"; the reference has no cache at all;
(4) the published ``mask_token_id`` (151,669) lies outside the held slice of
the vocabulary: the slice's LAST id stands for it (``assumed``);
(5) the confidence is the softmax of the raw float32 logits (greedy: no
temperature, top-k or top-p to filter first);
(6) the router's logits in float32 from float32 activations, an expert's
output weighted and summed in float32; default rotary frequencies only, a
``rope_scaling``, a bias or a sliding window is refused, not ignored;
(7) ties between equal confidences go to the LEFT.

**What a served token is held to** (``reference_logits``; ``systems
.ServeSystem.check`` reads row ``prompt - 1 + k`` for new token k and hands
over nothing of the program's choices). The function replays the blocks of
the sequence it is given, teacher-forced: a pass's logits at the positions
that pass unmasks are those positions' rows, put one row UP so the harness
finds them. Two discontinuities stand between a bf16 program and this
float32 pass, both valid on either side: a held expert at the router's cut
(measured on the chip and reported by ``benchmark/sdar_witness.py``; at
these widths a flip moves a logit by less than the margin) and WHICH
positions the first pass unmasks. Where the reference's own confidences
leave that open — a position inside the set and one outside it closer than
``POSITION_TIE`` — every set of the step's size that the tolerance admits
is tried, the later passes following the reference's own rule, and the
rows returned are those of the first set under which every token of the
block lies within ``MARGIN`` of its row's best (the reference's own set
first, and its own where none does). Only the static rule has alternatives:
the sequential one reads no confidence, and the dynamic one's threshold is
left to its own reading.

**The counts**: only matrix multiplications. A PASS over a block of Lb rows
reads every mixer, norm and router weight once, the head once, and of the
routed experts the EXPECTED number of distinct held ones that Lb rows' 8
choices each fall on (``experts_met``: 16 (1 - (1 - 8/128)^Lb) = 3.64 for 4
rows); a token costs ``denoising_steps`` passes / Lb. The COMMIT pass is
counted as free, so that a later change which fuses it with the next
block's first pass cannot read over 100%. The two kernels' own counts
(``block_attn_bytes``, ``block_flash_flops``) are the least the algorithm
can do at the lengths the chip really ran.
"""

import collections
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.llama import _f32, _rms_norm

Sizes = collections.namedtuple(
    "Sizes", "d layers heads kv dh expert held first router top_k vocab "
             "block steps remasking threshold mask_id")
EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")
ROW_BLOCK = 1024
# standard deviations of a row's router logits within which a held expert's
# side of the cut counts as open (the openPangu family's measure; here only
# reported, by the witness: a flip moves a logit by less than the margin)
TIE = 0.04
# how close a confidence inside the first pass's set and one outside it may
# lie for the choice of positions to count as open, relative: |c_in - c_out|
# <= POSITION_TIE * c_out. A bf16 program's confidence of a position differs
# from this pass's by up to 1.2-2.6% of its value (benchmark/sdar_witness.py
# on the chip, five runs, PR 45; 9% with float8 mixers), so two of them by
# ~5%: twice that
POSITION_TIE = 0.1
# systems.SERVE_LOGIT_MARGIN, which this module cannot import at load (the
# harness imports the families); tests/benchmark/test_sdar_family.py holds
# the two equal
MARGIN = 0.1


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m, serve = cfg["model"], cfg["serve"]
    held = m["num_experts"]
    cut = "num_experts" in cfg.get("reduced", ())
    return Sizes(
        m["hidden_size"], m["num_hidden_layers"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["moe_intermediate_size"],
        held, cfg.get("share", {}).get("experts_first", 0) if cut else 0,
        cfg["published"]["num_experts"] if cut else held,
        m["num_experts_per_tok"], m["vocab_size"],
        int(serve["block_length"]), int(serve["denoising_steps"]),
        serve["remasking"], float(serve.get("confidence_threshold", 0.9)),
        m["vocab_size"] - 1)


def _refuse_what_is_not_computed(cfg):
    m = cfg["model"]
    if m.get("rope_scaling") or m.get("attention_bias") \
            or m.get("use_sliding_window") or m.get("sliding_window") \
            or m.get("mlp_only_layers") or m.get("decoder_sparse_step", 1) != 1 \
            or m.get("hidden_act", "silu") != "silu" \
            or m.get("tie_word_embeddings") \
            or cfg["serve"]["remasking"] not in REMASKING:
        raise SystemExit(
            "benchmark: the sdar_moe family computes default rotary "
            "frequencies, no bias, no sliding window, routed experts in "
            "every layer, SiLU, an untied head and one of the three "
            f"remasking rules {REMASKING}; this file asks otherwise")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with per-head q/k norm, a softmax
    top-k router over the published width, this chip's experts, the
    block-causal mask and the block step; the decoding rule from the file's
    ``serve`` block. A serve system asks for the parameters in the type it
    serves (``families/olmoe.py``). No ``train`` system: the model trains on
    a noise schedule the catalog does not give."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    if kind != "serve":
        raise SystemExit("benchmark: the sdar_moe family is served only (its "
                         "training needs a noise schedule, which the catalog "
                         "lists under not_given)")
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    over = {"param_dtype": jnp.bfloat16} if cfg["serve"]["dtype"] == "bf16" \
        else {}
    return LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["max_position_embeddings"],
        n_embd=z.d, n_layer=z.layers, n_head=z.heads, n_kv_head=z.kv,
        head_dim=z.dh, intermediate_size=z.expert,
        rope_theta=m["rope_theta"], rms_norm_eps=m["rms_norm_eps"],
        tie_embeddings=False, qk_norm="head", n_experts=z.router,
        n_experts_per_tok=z.top_k, norm_topk_prob=m["norm_topk_prob"],
        experts_held=(z.first, z.held) if z.held < z.router else None,
        block_length=z.block, denoising_steps=z.steps, remasking=z.remasking,
        confidence_threshold=z.threshold, mask_token_id=z.mask_id, **over))


# ------------------------------------------------------ the plain reference
def _rotate_at(x, theta, positions):
    """Rotate-half rotary embedding of x (T, heads, Dh) at ``positions``
    (T,): pair i of a head is (x[i], x[i + Dh/2]), turned by position /
    theta^(2i/Dh)."""
    dh = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(x, blk, z, theta, eps, start, before):
    """-> (x + Attn(RMSNorm(x)), this call's k and v rows (T, KV heads,
    128)). The T rows lie at absolute positions ``start ..`` (``start`` may
    be traced); ``before``: None, or (k, v) rows of a whole sequence from
    position 0, each (S, KV heads, 128), of which those BEFORE ``start`` are
    seen (they lie in earlier blocks) and the rest are not."""
    T = x.shape[0]
    h = _rms_norm(x, blk["attn_norm_g"], eps)
    at = start + jnp.arange(T)
    q = (h @ _f32(blk["q_w"])).reshape(T, z.heads, z.dh)
    k = (h @ _f32(blk["k_w"])).reshape(T, z.kv, z.dh)
    v = (h @ _f32(blk["v_w"])).reshape(T, z.kv, z.dh)
    q = _rotate_at(_rms_norm(q, blk["q_norm_g"], eps), theta, at)
    k = _rotate_at(_rms_norm(k, blk["k_norm_g"], eps), theta, at)
    keys, values, key_at = k, v, at
    seen = jnp.ones(T, bool)
    if before is not None:
        earlier = jnp.arange(before[0].shape[0])
        keys = jnp.concatenate([before[0], k])
        values = jnp.concatenate([before[1], v])
        key_at = jnp.concatenate([earlier, at])
        seen = jnp.concatenate([earlier < start, seen])
    q = q.reshape(T, z.kv, z.heads // z.kv, z.dh)
    rb = next(b for b in range(min(T, ROW_BLOCK), 0, -1) if T % b == 0)

    def rows(j):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, j * rb, rb, 0)
        s = jnp.einsum("qgrd,kgd->grqk", cut(q), keys) / math.sqrt(z.dh)
        admitted = seen[None, :] & (
            key_at[None, :] // z.block <= cut(at)[:, None] // z.block)
        s = jnp.where(admitted[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1),
                          values)

    attn = jax.lax.map(rows, jnp.arange(T // rb)).reshape(T, z.heads * z.dh)
    return x + attn @ _f32(blk["o_w"]), (k, v)


def _route(h, router_w, z, renormalize, held):
    """-> (weights (T, k), chosen experts (T, k), ``distance`` (T, held):
    how far each held expert's router logit lies from the CUT (midway
    between the k-th and the next logit of the row), in standard deviations
    of the row's logits). ``held`` (T, held) int, -1 = the router's own: a
    held expert's side GIVEN (1 taken, 0 left out), the other places going
    to the best of the rest."""
    logits = h @ _f32(router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    mine = logits[:, z.first:z.first + z.held]
    if z.router > z.top_k:
        edge = jax.lax.top_k(logits, z.top_k + 1)[0][:, z.top_k - 1:]
        distance = jnp.abs(mine - jnp.mean(edge, axis=-1, keepdims=True)) \
            / jnp.std(logits, axis=-1, keepdims=True)
    else:
        distance = jnp.full(mine.shape, jnp.inf)
    # probabilities lie in (0, 1): a given side outranks, or is outranked
    # by, all
    key = probs.at[:, z.first:z.first + z.held].add(
        jnp.where(held > 0, 2.0, jnp.where(held == 0, -2.0, 0.0)))
    chosen = jax.lax.top_k(key, z.top_k)[1]
    weights = jnp.take_along_axis(probs, chosen, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, chosen, distance


def _experts(h, weights, chosen, stacked, layer, z):
    """The chosen experts HELD here, one at a time, each sliced out of the
    stacked (L, held, ...) leaves; a token that did not choose one, or chose
    one held elsewhere, adds exactly zero."""
    def one(e, acc):
        take = lambda name: _f32(jax.lax.dynamic_slice(
            stacked[name], (layer, e, 0, 0),
            (1, 1) + stacked[name].shape[2:])[0, 0])
        y = (jax.nn.silu(h @ take("expert_gate_w")) * (h @ take("expert_up_w"))
             ) @ take("expert_down_w")
        mine = chosen == z.first + e            # (T, k): at most one True
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1, keepdims=True)
        return acc + jnp.where(jnp.any(mine, axis=-1, keepdims=True),
                               w * y, 0.0)

    return jax.lax.fori_loop(0, z.held, one, jnp.zeros_like(h))


def reference_forward(params, ids, masked, cfg, start=0, prefix=None,
                      held=None):
    """ids (T,) int32, masked (T,) bool -> (float32 logits (T, vocab), row
    i the prediction of the token AT position ``start + i``; per layer the
    rows' ``k`` / ``v`` (L, T, KV heads, 128), the routers' ``chosen``
    experts (L, T, k) and ``distance`` (L, T, held) of each held expert's
    logit from the row's cut). ``prefix``: None, or (k, v) each (L, S, KV
    heads, 128), a whole sequence's rows from position 0, of which the T
    rows see those before ``start`` (the T rows are then one block or more
    at ``start ..``, ``start`` a whole number of blocks, traced or not).
    ``held`` (L, T, held) int, None = all -1: a held expert's side given."""
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    theta, eps = float(m["rope_theta"]), float(m["rms_norm_eps"])
    blocks = params["blocks"]
    stacked = {n: blocks[n] for n in EXPERT_LEAVES}
    sliced = {n: v for n, v in blocks.items() if n not in EXPERT_LEAVES}
    if held is None:
        held = jnp.full((z.layers, ids.shape[0], z.held), -1, jnp.int8)

    def block(x, xs):
        blk, layer, sides, before = xs
        x, kv = _attention(x, blk, z, theta, eps, start, before)
        h = _rms_norm(x, blk["mlp_norm_g"], eps)
        weights, chosen, distance = _route(h, blk["router_w"], z,
                                           m["norm_topk_prob"], sides)
        return x + _experts(h, weights, chosen, stacked, layer, z), \
            {"k": kv[0], "v": kv[1], "chosen": chosen, "distance": distance}

    with jax.default_matmul_precision("highest"):
        read = jnp.where(masked, jnp.int32(z.mask_id), ids)
        x = _f32(params["wte"])[read]
        x, kept = jax.lax.scan(
            block, x, (sliced, jnp.arange(z.layers), held, prefix))
        x = _rms_norm(x, params["norm_g"], eps)
        return x @ _f32(params["lm_head"]), kept


def transfer_counts(z):
    """Positions each denoising pass of a block unmasks at least."""
    base, more = divmod(z.block, z.steps)
    return [base + (s < more) for s in range(z.steps)]


def unmask(conf, masked, n, z):
    """numpy: which of a block's masked positions a pass unmasks (the
    module's docstring; ties to the left, never a position not masked)."""
    where = np.flatnonzero(masked)
    if z.remasking == "sequential":
        picked = where[:n]
    else:
        best = sorted(where, key=lambda i: (-conf[i], i))[:n]
        high = [i for i in where if conf[i] > z.threshold]
        picked = high if z.remasking == "low_confidence_dynamic" \
            and len(high) >= n else best
    move = np.zeros_like(masked)
    move[np.asarray(picked, dtype=int)] = True
    return move


def reference_generate(params, prompt, new_tokens, cfg, eos=None):
    """Greedy generation by full forward passes of the whole sequence, no
    cache (the module's docstring) -> (the ``new_tokens`` tokens, the log:
    one (block, pass, positions unmasked, the block's logits) a pass). The
    sequence is held at its final length all along: under the block-causal
    mask no row sees a later block, so what stands there changes nothing."""
    z = _sizes(cfg)
    prompt = np.asarray(prompt, dtype=np.int32)
    P = len(prompt)
    n_blocks = -(-(P + new_tokens) // z.block)
    ids = np.zeros(n_blocks * z.block, np.int32)
    ids[:P] = prompt
    masked = np.arange(len(ids)) >= P
    forward = jax.jit(lambda ids, masked: reference_forward(
        params, ids, masked, cfg)[0])
    log, counts = [], transfer_counts(z)
    for b in range(P // z.block, n_blocks):
        at = slice(b * z.block, (b + 1) * z.block)
        for s in range(z.steps):
            if not masked[at].any():
                break
            logits = np.asarray(forward(ids, masked))[at].astype(np.float64)
            x0 = logits.argmax(axis=-1)
            p = np.exp(logits - logits.max(axis=-1, keepdims=True))
            conf = p[np.arange(z.block), x0] / p.sum(axis=-1)
            move = unmask(conf, masked[at], counts[s], z)
            ids[at] = np.where(move, x0, ids[at])
            masked[at] &= ~move
            log.append((b, s, np.flatnonzero(move), logits))
        new = ids[max(P, b * z.block):(b + 1) * z.block]
        if eos is not None and (new == eos).any():
            first = max(P, b * z.block) + int(np.argmax(new == eos))
            ids[first:] = eos
            break
    return ids[P:P + new_tokens], log


def _first_sets(z):
    """Every set of the first pass's size among a block's positions, as
    (sets, Lb) bool, the order of ``itertools.combinations``."""
    n = transfer_counts(z)[0]
    sets = np.zeros((math.comb(z.block, n), z.block), bool)
    for i, members in enumerate(itertools.combinations(range(z.block), n)):
        sets[i, list(members)] = True
    return sets


def _rank_unmask(conf, masked, n, z):
    """``unmask`` in ``jnp`` for ONE block (conf, masked (Lb,); n traced)."""
    if z.remasking == "sequential":
        return masked & (jnp.cumsum(masked) <= n)
    conf = jnp.where(masked, conf, -jnp.inf)
    at = jnp.arange(conf.shape[0])
    above = (conf[None, :] > conf[:, None]) | (
        (conf[None, :] == conf[:, None]) & (at[None, :] < at[:, None]))
    top = masked & (jnp.sum(above, axis=1) < n)
    if z.remasking == "low_confidence_static":
        return top
    high = masked & (conf > z.threshold)
    return jnp.where(jnp.sum(high) >= n, high, top)


def block_rows(params, ids, cfg, start, first=None, logits0=None):
    """The rows a block's tokens are held to: the block at ``start ..
    start + Lb - 1`` (traced) of ``ids`` (T,) replayed teacher-forced from
    all masked, ``first`` (Lb,) bool the set the first pass unmasks (None:
    by the rule from this pass's own confidences), the later passes by the
    rule -> (rows (Lb, vocab): each position's logits in the pass that
    unmasked it, the first pass's own set (Lb,) bool, its confidences, its
    logits (Lb, vocab): ``logits0``, where a caller has them already)."""
    z = _sizes(cfg)
    T = ids.shape[0]
    at = jnp.arange(T)
    inside = lambda own: jnp.zeros(T, bool).at[start + jnp.arange(z.block)
                                               ].set(own)
    masked = at >= start            # the block and whatever follows it
    rows = jnp.zeros((z.block, z.vocab), jnp.float32)
    left = jnp.ones(z.block, bool)
    own = conf0 = None
    for s, n in enumerate(transfer_counts(z)):
        logits = logits0 if s == 0 and logits0 is not None else \
            jax.lax.dynamic_slice_in_dim(
                reference_forward(params, ids, masked, cfg)[0], start, z.block)
        if s == 0:
            logits0 = logits
        conf = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
        move = _rank_unmask(conf, left, n, z)
        if s == 0:
            own, conf0 = move, conf
            if first is not None:
                move = first
        rows = jnp.where(move[:, None], logits, rows)
        left = left & ~move
        masked = masked & ~inside(move)
    return rows, own, conf0, logits0


def reference_logits(params, ids, cfg, prompt=None):
    """What ``systems.ServeSystem.check`` holds a served token to (the
    module's docstring): ``ids`` (T,) = a prompt of ``prompt`` tokens (None:
    ``systems.CHECK_PROMPT``, 64; whole blocks) and the tokens served after
    it (whole blocks) -> (T, vocab), row ``p - 1`` the row the token at
    position p >= prompt is held to, zeros elsewhere."""
    z = _sizes(cfg)
    P = 64 if prompt is None else int(prompt)
    T = ids.shape[0]
    if P % z.block or (T - P) % z.block:
        raise SystemExit(f"benchmark: the sdar_moe check replays whole blocks "
                         f"of {z.block}; it was given {P} + {T - P} tokens")
    sets = jnp.asarray(_first_sets(z))

    def shortfall(rows, toks):
        return jnp.max(jnp.max(rows, axis=-1)
                       - jnp.take_along_axis(rows, toks[:, None], axis=-1)[:, 0])

    def one_block(start):
        toks = jax.lax.dynamic_slice_in_dim(ids, start, z.block)
        rows, _, conf, logits0 = block_rows(params, ids, cfg, start)
        if z.remasking != "low_confidence_static" or z.steps == 1:
            return rows
        # a set is admissible where no position outside it is more
        # confident than one inside it by more than the tolerance
        def other(first):
            inner = jnp.min(jnp.where(first, conf, jnp.inf))
            outer = jnp.max(jnp.where(first, -jnp.inf, conf))
            open_ = outer - inner <= POSITION_TIE * outer
            alt = block_rows(params, ids, cfg, start, first, logits0)[0]
            return alt, jnp.where(open_, shortfall(alt, toks), jnp.inf)

        alts, shorts = jax.lax.map(other, sets)
        best = jnp.argmax(shorts <= MARGIN)         # the first that passes
        use = (shortfall(rows, toks) > MARGIN) & jnp.any(shorts <= MARGIN)
        return jnp.where(use, alts[best], rows)

    starts = P + z.block * jnp.arange((T - P) // z.block)
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.map(one_block, starts).reshape(T - P, z.vocab)
    return jnp.zeros((T, z.vocab), jnp.float32).at[P - 1:T - 1].set(rows)


def reference_loss(params, ids, cfg):
    """No loss: the model trains on a noise schedule the catalog lists under
    ``not_given``; the next-token loss is not its loss and no cell trains
    it."""
    raise SystemExit("benchmark: the sdar_moe family has no training loss "
                     "(its noise schedule is not given); it is served only")


# ----------------------------------------- operations and bytes from shapes
def _mixer_params(z):
    return 2 * z.d * z.heads * z.dh + 2 * z.d * z.kv * z.dh


def _expert_params(z):
    return 3 * z.d * z.expert


def _norm_params(z):
    return z.layers * (2 * z.d + 2 * z.dh) + z.d


def held_params(cfg):
    """Every parameter this chip holds: 4,620,433,408 at the published
    widths and the stated share."""
    z = _sizes(cfg)
    return z.layers * (_mixer_params(z) + z.d * z.router
                       + z.held * _expert_params(z)) \
        + _norm_params(z) + 2 * z.vocab * z.d


def experts_met(cfg, rows=None):
    """Distinct routed experts HELD HERE that a pass over ``rows`` rows
    (None: one block) is expected to read in one layer: ``held x (1 - (1 -
    top_k / router)^rows)`` (3.64 of 16 for 4 rows; 1.0 for one row)."""
    z = _sizes(cfg)
    rows = z.block if rows is None else rows
    return z.held * (1.0 - (1.0 - z.top_k / z.router) ** rows)


def pass_params(cfg):
    """Parameters ONE forward pass over a block must read: every mixer,
    router and norm weight, the expected distinct held experts, the head."""
    z = _sizes(cfg)
    return z.layers * (_mixer_params(z) + z.d * z.router
                       + experts_met(cfg) * _expert_params(z)) \
        + _norm_params(z) + z.d * z.vocab


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication for ONE row of a
    pass, on this chip: the mixer, the router, the expected share of routed
    experts (``top_k x held / router`` = 1 a layer) and the head."""
    z = _sizes(cfg)
    return z.layers * (_mixer_params(z) + z.d * z.router
                       + z.top_k * z.held / z.router * _expert_params(z)) \
        + z.d * z.vocab


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes of K and V one cached position holds across all layers
    (98,304 at the published widths)."""
    z = _sizes(cfg)
    return z.layers * 2 * z.kv * z.dh * itemsize


def passes_per_token(cfg):
    """Denoising passes a generated token costs: ``denoising_steps`` a
    block of ``block_length`` tokens; the commit counted as free (the
    module's docstring)."""
    z = _sizes(cfg)
    return z.steps / z.block


def decode_flops_per_token(cfg):
    """A pass multiplies each of its Lb rows through the weights a row
    meets; a token costs ``denoising_steps`` / Lb passes."""
    z = _sizes(cfg)
    return passes_per_token(cfg) * z.block * 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """HBM bytes one generated token needs: ``denoising_steps`` / Lb passes,
    each the weights a pass reads once and the K/V of the ``context``
    positions its block attends to."""
    return passes_per_token(cfg) * (
        pass_params(cfg) * itemsize
        + context * kv_bytes_per_position(cfg, itemsize))


def block_attn_bytes(cfg, context, itemsize=2):
    """K/V bytes ONE pass's attention reads, all layers: the ``context``
    slots valid when the block's rows are in (its own among them), each
    once for all of the block's rows and heads."""
    return context * kv_bytes_per_position(cfg, itemsize)


def block_attn_flops(cfg, context):
    """FLOPs of one pass's attention, all layers: Lb rows x heads, scores
    and weighted sum over 128 columns of ``context`` slots."""
    z = _sizes(cfg)
    return z.layers * z.block * z.heads * context * 4 * z.dh


def block_flash_flops(cfg, seq):
    """The block-causal prefill's attention, forward, all layers: every
    query head against every position of its own and earlier blocks: the
    causal half of the square plus the blocks' upper triangles."""
    z = _sizes(cfg)
    pairs = seq * (seq + z.block) / 2
    return z.layers * z.heads * pairs * 4 * z.dh


def block_flash_bytes(cfg, seq, itemsize=2):
    """q, k, v read and o written once, all layers (K/V at the query heads'
    width: the program repeats them before its kernel, the algorithm need
    not)."""
    z = _sizes(cfg)
    return z.layers * 2 * seq * (z.heads + z.kv) * z.dh * itemsize


attention_flops_fwd = block_flash_flops


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs of one token through the trunk at sequence
    length ``seq``: 6 x the matmul parameters a row meets here, plus
    attention at 3x its forward. (No cell trains this configuration: the
    harness's interface asks every family for the function.)"""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq
