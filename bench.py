#!/usr/bin/env python
"""Headline benchmark: GPT-2 pretraining throughput + MFU on TPU.

Prints one JSON line per benched preset: the HEADLINE (gpt2-760m) first,
then gpt2-xl and gpt2-1.3b, then the SAME headline line repeated last so a
tail-line parser records it: {"metric", "value", "unit", "vs_baseline"}.
Baseline: the north-star from BASELINE.md — ≥50% MFU for GPT-2-class ZeRO-3
pretraining (the reference's best published efficiency is 52% of peak on V100,
docs/_posts/2020-05-19-bert-record.md:13). vs_baseline = MFU / 0.50.

Default on TPU: the BASELINE ladder — the gpt2-760m headline, the offload
family (gpt2-xl 1.5B north star, gpt2-1.3b, llama3.2-1b — GQA, 128k
vocab; all host-offload-backed on one 16G chip), bert-large (the
reference's record family, at seq512 AND its published seq128 record
config), gpt2-moe-125m (Switch-8-expert milestone), a serving-decode line
(BENCH_SERVE_LINE=0 skips), a v5e-64 north-star projection, headline
repeated. The ladder runs under BENCH_DEADLINE_S (default 1620s) with an
explicit-skip policy, per-line regression guards against the EXPECTED
ledger (<70% of expectation re-measures once; <85% marks
"regression": true), and SIGTERM/SIGINT handlers that re-print the
headline so a driver timeout still parses the right tail line
(BENCH_r04 rc=124 post-mortem).
Set BENCH_MODEL to bench exactly one preset (gpt2-*/gpt2-moe-*/llama-*/
bert-*), BENCH_SUITE=0 to skip the extra presets.

Perf ledger (docs/BENCH.md): every line runs under a telemetry session
and appends a structured entry (model/config/env/seed/git_rev/fingerprint
fields + per-step samples + span/memory/flops/exposed-comm attribution)
to BENCH_LEDGER (default ./perf_ledger.jsonl); the legacy metric string
stays for tail-line parsers. BENCH_PERF=0 opts out (bare measurement).
`python bench.py --smoke [--ledger PATH]` is the CI-sized CPU dry run of
the whole pipeline; `ds_perf gate --baseline BENCH_r05.json` fails a
build on a headline regression. `--devices N` (BENCH_DEVICES) fakes an
N-device CPU mesh (--xla_force_host_platform_device_count) so the
ZeRO-3/dp sharding paths run off-TPU; `--overlap overlapped|serial|off`
(BENCH_OVERLAP) adds the `overlap` ds_config block — run the same line
under `serial` then `overlapped` and `ds_perf diff --metric exposed_comm`
prices the hidden-collectives win from the two ledger entries. `--sdc`
(BENCH_SDC=1) arms the ds_sentry `sdc` block (replay audits every
BENCH_SDC_INTERVAL steps, default 2) and ASSERTS the recorded entry
prices the defense: an `audit` goodput bucket plus an `sdc_overhead`
attribution below audit_interval^-1 of wall — the number `ds_perf gate
--metric sdc_overhead` then regresses on. `--blackbox` (BENCH_BLACKBOX=1;
default ON under --smoke) arms the ds_blackbox `blackbox` flight-recorder
block and ASSERTS the entry prices it: a `blackbox_overhead` attribution
under 0.5% of wall plus zero incident bundles on the clean run — the
number `ds_perf gate --metric blackbox_overhead` then regresses on.

Env knobs: BENCH_MODEL, BENCH_BS (per-chip microbatch), BENCH_SEQ,
BENCH_STEPS, BENCH_GAS, BENCH_REMAT (none|full|dots|attn|attn_mlp; default
attn for decoders, none for bert), BENCH_OFFLOAD (none|cpu), BENCH_UNROLL,
BENCH_FLASH_BLOCK, BENCH_FLASH (bert einsum switch), BENCH_EXPERTS (moe
bank size), BENCH_HEADS (head-count override at fixed n_embd; gpt2/bert
only — params/flops are head-count invariant there), BENCH_VOCAB (vocab
override; 50304 = 128-aligned measured no change vs 50257 — XLA already
handles the pad), BENCH_NORTHSTAR_BS (grad-only batch for the 64-chip
compute-regime measurement in the projection line; default 14).
Measured per-family
sweet spots on one v5e chip:
- gpt2-760m: 0.567-0.569 MFU (bs=12, remat='attn', flash_block=1024 — the
  full-sequence tile; 512 measured 0.521, 256 regresses to 0.461 — and
  n_head=4, head_dim=384: the r5 fat-head sweep 12x128 0.536 < 6x256
  0.545 < 3x512 0.549 < 4x384 0.569, 2x768 OOM; bs=14 0.554. The r4
  lever head_dim=128 (12 heads, 0.536) and the GPT-2-paper-ish 16x96
  (0.512) are both superseded — see registry.TPU_HEAD_OVERRIDES).
  Negative results from the r4 sweeps, so they are not re-probed: bs=14
  0.520, bs=16 0.512 (fits only with remat_loss_chunks), gas=2 0.488 /
  gas=4 0.496 (~8%/micro accumulation-scan tax; unrolling the gas scan
  OOMs — XLA interleaves the unrolled micros), layer-scan unroll=2
  0.523 / 4 0.448, remat='attn_mlp' (save gelu outs too) OOM at bs=12
  and 0.442 at bs=8 — the raw-util loss below bs=12 outweighs the saved
  MLP recompute; remat='dots'+offload crashes the XLA compile helper;
  remat='attn'+offload gas=8 0.427 (host round-trip tax beats the
  recompute saving at this size); forced triangular flash at nq=2
  (DS_TPU_FLASH_TRI_MIN=2, fb=512) 0.510; BENCH_VOCAB=50304 no change.
- gpt2-1.3b / gpt2-xl (ZeRO-Offload ladder): 0.386 / 0.243 MFU at
  gas=32/16 — the host round-trip amortized over a GPT-2-paper-sized
  token batch. 1.3b defaults to stream_overlap (double-buffered host
  streaming, +0.018 over serial, stable over repeats); xl keeps serial
  (overlap faults its worker or collapses 3x) and gas=24/32 fault too.
  r5 xl head-layout sweep (grad-only @bs=14, remat='attn', the n_embd=1600
  divisor ladder — param/flop-invariant, architecture differs): 25x64
  0.429 < 20x80 0.454 < 10x160 0.468 < 8x200 ~= 5x320, both 0.496-0.504
  over 5 samples each (8x200 needs fb=1024; 4x400 exceeds the flash
  kernel's vmem scratch; bs=15/16 and unroll=8 OOM HBM; unroll 2/4 and
  fb 256/512 within noise of default). 0.499+-0.003 is the measured xl
  single-chip compute ceiling of this kernel/remat recipe — and the term
  that pins the v5e-64 projection at ~0.497: comm+sharded-update cost only
  ~0.002 at gas=16. The remaining gap to 0.52+ is the remat='attn'
  recompute tax plus n_embd=1600 spanning 12.5 MXU tiles. The xl
  ladder line + northstar projection run 5x320
  (registry.TPU_HEAD_OVERRIDES); BENCH_HEADS=25 benches canonical.
  Reproducibility (r4 post-mortem): llama3.2-1b measured 0.136 under the
  r4 driver vs 0.341 standalone same config — environmental collapse, not
  config drift; the ladder now re-measures any line <70% of EXPECTED and
  flags <85% as regression.
- bert-large (the reference's own headline family): 0.576 MFU at
  bs=14/seq=512/gas=4 — 2 heads x head_dim 512 (r5 fat-head sweep: 8x128
  0.568, 4x256 0.568; canonical 16x64 measured 0.463), no remat +
  unrolled layer loop + MLM head over gathered masked positions (honest
  accounting: skipped head flops subtracted); flash beats einsum at
  seq=512. At the reference record's own seq=128 phase-1 config: 0.694
  (bs=48, gas=8, 2x512; 8x128 measured 0.614) vs the published
  64 TFLOPS/V100 ≈ 51% — beats the reference's record efficiency at the
  same seq/batch/gas config, with the TPU-native head layout (the
  canonical 16x64 architecture the record ran measures ~0.46-0.48 here:
  its knob sweep — einsum 0.416, fb256 0.379, fb128 0.271, bs12 0.460,
  bs16 0.454 — is ceiling-bound by head_dim 64 halving MXU contraction
  utilization).
- gpt2-moe-125m (Switch-8): 0.390 MFU at bs=12 with the MXU-aligned
  6x128 head layout (12x64 canonical: 0.328; bs=16 0.370, bs=24 0.200).
- llama3.2-1b (GQA 32h/8kv, V=128k, tied): 0.341 MFU at bs=12/gas=32,
  offload-backed (bs=8 0.314, bs=16 faults the worker; stream_overlap
  measured +0.004 — within noise, left off).
- serving (BENCH_SERVE=1, gpt2-760m bf16 greedy, prompt 128 gen 128,
  prefill measured separately and subtracted): pure decode 6.8k tok/s at
  B=32 (MBU 0.70), 13.7k at B=128 (MBU 0.83) after moving the stacked KV
  cache into the decode scan's carry (the xs/ys layout copied the whole
  cache every token: 2.2k tok/s). int8 weights measured no change
  (decode is cache+weight-stream bound, not weight-only). llama3.2-1b GQA
  decode: 6.3k tok/s at B=32/128/128 (MBU 0.66). (These predate the
  decode kernel and folded cache of PR 25, which every TPU program now
  takes: PERF.md.)
"""

import json
import math
import os
import sys
import tempfile
import time
from functools import partial

# --smoke: CI-sized dry run of the instrumented bench — tiny model, two
# timed steps, CPU backend, suite off — so a tier-1 test can assert the
# ledger plumbing end-to-end without a TPU. Parsed BEFORE the jax import
# (JAX_PLATFORMS must be set before backend init; platforms that pin the
# backend also honor the jax.config update in main()).
SMOKE = "--smoke" in sys.argv[1:]
if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("BENCH_MODEL", "gpt2-tiny")
    # 3 timed steps: the minimum per-side sample count at which the
    # ledger's t gate has power (ledger.MIN_POWER_SAMPLES) — smoke
    # entries must be gateable with noise bounds, not just thresholds
    os.environ.setdefault("BENCH_STEPS", "3")
    os.environ.setdefault("BENCH_SEQ", "128")
    os.environ.setdefault("BENCH_BS", "2")
    os.environ["BENCH_SUITE"] = "0"
if "--ledger" in sys.argv[1:]:
    _i = sys.argv[1:].index("--ledger") + 1   # first occurrence, args only
    if _i + 1 >= len(sys.argv):
        sys.exit("bench.py: --ledger requires a path argument")
    os.environ["BENCH_LEDGER"] = sys.argv[_i + 1]
# --devices N (or BENCH_DEVICES): simulated multi-device mode — N virtual
# CPU devices via --xla_force_host_platform_device_count, so the ZeRO/dp
# sharding paths (and the overlap engine's gather schedules) are
# exercisable off-TPU: `bench.py --smoke --devices 8` runs the gpt2-tiny
# line as a real ZeRO-3 8-way job in CI. Must land in XLA_FLAGS before the
# jax import below initializes the backend.
if "--devices" in sys.argv[1:]:
    _i = sys.argv[1:].index("--devices") + 1
    if _i + 1 >= len(sys.argv):
        sys.exit("bench.py: --devices requires a count argument")
    os.environ["BENCH_DEVICES"] = sys.argv[_i + 1]
_devices = int(os.environ.get("BENCH_DEVICES", 0))
if _devices > 1:
    _fl = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _fl:
        os.environ["XLA_FLAGS"] = (
            f"{_fl} --xla_force_host_platform_device_count={_devices}".strip())
    os.environ["JAX_PLATFORMS"] = "cpu"   # simulated devices are a CPU mode
# --overlap MODE (or BENCH_OVERLAP): add the `overlap` ds_config block to
# every engine-backed line. "overlapped" = the restructured schedule,
# "serial" = the measured un-overlapped baseline whose gather phase lands
# as comm spans — running the same line under both yields the two ledger
# entries whose exposed_comm_us_per_step delta prices the overlap win
# (`ds_perf diff --metric exposed_comm`). Unset = no block (strict no-op).
if "--overlap" in sys.argv[1:]:
    _i = sys.argv[1:].index("--overlap") + 1
    if _i + 1 >= len(sys.argv):
        sys.exit("bench.py: --overlap requires a mode "
                 "(overlapped|serial|off)")
    os.environ["BENCH_OVERLAP"] = sys.argv[_i + 1]
# --wire MODE (or BENCH_WIRE): add the `wire` ds_config block (ds_wire —
# qwZ/hpZ/qgZ wire-speed ZeRO collectives) to every engine-backed line.
# "off" arms NOTHING but still applies the same intra-host mesh factoring
# (tpu.ici) as the quantized modes, so the on/off pair shares one
# mesh_axes identity and `ds_perf diff/gate --metric static_comm_bytes`
# compares them — the wire knob itself is stamped into the metric string,
# config, fingerprint and the entry's `wire_mode`. Unset = no block AND
# no factoring (strict no-op). BENCH_WIRE_ICI overrides the auto host
# split (default: half the devices on a single-process simulated mesh).
if "--wire" in sys.argv[1:]:
    _i = sys.argv[1:].index("--wire") + 1
    if _i + 1 >= len(sys.argv):
        sys.exit("bench.py: --wire requires a mode (off|qwz|qwz+hpz|full)")
    os.environ["BENCH_WIRE"] = sys.argv[_i + 1]
# --sdc (or BENCH_SDC=1): arm the ds_sentry `sdc` block on every
# engine-backed line — deterministic replay audits every
# BENCH_SDC_INTERVAL steps (default 2: the smoke's 3-step timed window
# must hold at least one audit) + the in-step state checksum. The line
# then asserts its own ledger entry carries the `audit` goodput bucket
# and an `sdc_overhead` attribution under the audit_interval^-1 budget.
# Unset = no block (strict no-op: the sdc module is never imported).
if "--sdc" in sys.argv[1:]:
    os.environ["BENCH_SDC"] = "1"
# --gray (or BENCH_GRAY=1): arm the ds_gray `gray` block on every
# engine-backed line in unconditional-probe mode — a microprobe every
# BENCH_GRAY_EVERY steps (default 2: the smoke's 3-step timed window
# must hold at least one probe). The line then asserts its own ledger
# entry carries the `probe` goodput bucket and a `gray_overhead`
# attribution under the 2%-of-wall budget (the contract
# `ds_perf gate --metric gray_overhead` holds in CI).
# Unset = no block (strict no-op: the gray module is never imported).
if "--gray" in sys.argv[1:]:
    os.environ["BENCH_GRAY"] = "1"
# --blackbox (or BENCH_BLACKBOX=1; DEFAULT ON under --smoke): arm the
# ds_blackbox `blackbox` block on every engine-backed line — the
# always-on flight recorder whose ring append rides the step path. The
# line then asserts its own ledger entry carries a `blackbox_overhead`
# attribution under the 0.5%-of-wall budget (the contract `ds_perf gate
# --metric blackbox_overhead` holds in CI): "always-on" is only
# defensible if it is effectively free, so the smoke prices it on every
# run. BENCH_BLACKBOX=0 opts out (strict no-op: the blackbox module is
# never imported).
if "--blackbox" in sys.argv[1:]:
    os.environ["BENCH_BLACKBOX"] = "1"
if SMOKE:
    os.environ.setdefault("BENCH_BLACKBOX", "1")

import jax
import numpy as np

if SMOKE:
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

# Perf-ledger instrumentation (BENCH_PERF=0 opts out): every line runs
# under a telemetry session + the perf ds_config block, the printed JSON
# becomes a STRUCTURED ledger entry (model/config/env/seed/git_rev/
# fingerprint as fields, per-step samples for ds_perf's noise bounds,
# span/memory/flops/exposed-comm attribution) appended to BENCH_LEDGER
# (default ./perf_ledger.jsonl). The legacy {"metric","value","unit",
# "vs_baseline"} keys stay — tail-line parsers keep working unchanged.
PERF = os.environ.get("BENCH_PERF", "1") != "0"
LEDGER = os.environ.get("BENCH_LEDGER", "perf_ledger.jsonl")
TELEMETRY_ROOT = os.environ.get(
    "BENCH_TELEMETRY_DIR",
    os.path.join(tempfile.gettempdir(), "bench_telemetry"))
_RUN_SEQ = 0    # per-process run_one counter: unique telemetry dirs


def _ledger_append(entry):
    """Best-effort direct ledger append (fail/skip lines and the engine-less
    serving/rlhf/projection lines; engine-backed lines append through
    perf_record)."""
    if not PERF:
        return entry
    try:
        from deepspeed_tpu.perf.ledger import append_entry

        return append_entry(LEDGER, entry)
    except Exception as e:
        print(f"# perf ledger append failed: {e}", file=sys.stderr)
        return entry


def _structured(line, model=None, config=None, seed=0):
    """Attach the structured identity fields to an engine-less line
    (serving / rlhf / projection): model, knobs, env, seed, git rev,
    config fingerprint — everything except engine attribution."""
    if not PERF:
        return line
    try:
        from deepspeed_tpu.perf.ledger import git_rev
        from deepspeed_tpu.resilience.consistency import config_fingerprint

        line = dict(line)
        line["model"] = model
        line["config"] = dict(config or {})
        line["env"] = {"backend": jax.default_backend(),
                       "n_dev": len(jax.devices()),
                       "jax": jax.__version__,
                       "python": sys.version.split()[0]}
        line["seed"] = seed
        line["git_rev"] = git_rev()
        line["fingerprint"] = config_fingerprint(
            {"bench": line.get("metric", "").split(" (", 1)[0],
             "config": line["config"]})
        return _ledger_append(line)
    except Exception as e:
        print(f"# perf structuring failed: {e}", file=sys.stderr)
        return line


def _release(engine):
    """Drop an engine's device memory: state, compiled programs (their
    constants pin buffers), and jit caches."""
    import gc

    engine.state = None
    engine.invalidate_compiled()
    jax.clear_caches()
    gc.collect()


def run_one(model_name: str, on_tpu: bool, n_dev: int) -> dict:
    import dataclasses

    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models.gpt2 import GPT2Model, PRESETS, synthetic_lm_batch

    # model-family registry shared with ds_tune (models/registry.py):
    # gpt2-* (default flagship), gpt2-moe-* (Switch-style top-1 expert bank
    # on every other block — the BASELINE "Switch-8-expert MoE" milestone;
    # MFU counts each token's ONE routed expert, honest w.r.t. useful math),
    # llama-*, bert-* (the reference's own headline benchmark family)
    from deepspeed_tpu.models.registry import resolve_family

    model_cls, make_batch, PRESETS = resolve_family(
        model_name, moe_experts=int(os.environ.get("BENCH_EXPERTS", 8)))

    config = PRESETS[model_name]
    heads = int(os.environ.get("BENCH_HEADS", 0))
    if heads and model_name.startswith("llama"):
        # LlamaConfig.__post_init__ has already resolved n_kv_head from the
        # preset's n_head: replacing n_head would silently flip the model to
        # GQA with a different kv_dim (params/flops NOT invariant there)
        raise ValueError("BENCH_HEADS supports gpt2/bert families only")
    if heads:
        # head-count override at constant n_embd: params and flops_per_token
        # are head-count invariant, so MFU stays comparable; head_dim=128
        # (the MXU-native lane width) is the TPU-first choice where the
        # GPT-2 paper shapes give 96 or 100
        if config.n_embd % heads:
            raise ValueError(f"BENCH_HEADS={heads} does not divide "
                             f"n_embd={config.n_embd}")
        config = dataclasses.replace(config, n_head=heads)
    vocab = int(os.environ.get("BENCH_VOCAB", 0))
    if vocab:
        # e.g. 50304 = 50257 rounded up to the 128-lane boundary (nanoGPT's
        # trick): the pad keeps the logits matmul tile-aligned without an
        # XLA pad-copy of the embedding table each step
        config = dataclasses.replace(config, vocab_size=vocab)
    if not heads and not model_name.startswith("llama") and on_tpu:
        # TPU-native pretrain head layout (param/flop invariant, architecture
        # differs — the relayout is LOGGED for reproducibility): head_dim 128
        # where n_embd allows (760m 16->12 heads, bert-large 16->8, moe 12->6),
        # measured per-preset override where it doesn't (gpt2-xl 25x64 ->
        # 5x320: the 64-wide contractions waste half of every MXU pass; see
        # registry.TPU_HEAD_OVERRIDES for the sweep). ds_tune applies the
        # same helper so tuner and bench agree; BENCH_HEADS=25 etc. benches
        # a canonical layout instead.
        from deepspeed_tpu.models.registry import tpu_native_layout
        config = tpu_native_layout(config, model_name,
                                   log=lambda m: print(f"# {m}",
                                                       file=sys.stderr))
    # measured per-family sweet spots on one v5e chip (see docstring):
    # decoders want 'attn' remat (save flash outputs, recompute the cheap
    # matmul chain); bert-large fits WITHOUT remat at bs=12 once the layer
    # loop is unrolled and the MLM head gathers masked positions
    bert = model_name.startswith("bert")
    big = model_name in ("gpt2-1.3b", "gpt2-xl", "gpt2-2.7b", "gpt2-6.7b",
                         "llama3.2-1b")
    remat = os.environ.get("BENCH_REMAT", "none" if bert else "attn")
    config = dataclasses.replace(config, remat=remat if remat != "none" else False)
    small_lm = (model_name.startswith(("gpt2", "bert")) and not big)
    if small_lm and on_tpu:
        # MEASURED small presets fit HBM with slack: skip the loss-chunk
        # remat and keep the saved fp32 logits (0.525 -> 0.535 on the 760m
        # headline). The offload-backed big models and the llama family
        # (llama3's V=128k logit residuals are GBs/chip) keep the default
        # True — their peak is the binding constraint.
        config = dataclasses.replace(config, remat_loss_chunks=False)
    seq = int(os.environ.get("BENCH_SEQ", min(1024, config.n_positions)))
    default_bs = 12 if on_tpu else 2
    if bert and on_tpu:
        # seq512 peak: bs=14 (0.561; 12 gives 0.553, 16 0.553). The seq128
        # record config (BENCH_SEQ=128) peaks at bs=48 (0.611; 64 0.604).
        default_bs = 14 if seq >= 512 else 48
    if big and on_tpu:
        # offload-backed: bigger microbatches amortize the streamed update
        # over more tokens. Measured peaks: 1.3b bs=16 (0.392-0.394 MFU),
        # xl bs=14 (0.252-0.255; with the loss-chunk remat freeing ~2.9G it
        # now completes 2 of 3 runs instead of faulting outright) — but both
        # still intermittently crash the TPU worker, so the DEFAULTS derate
        # one notch to the never-faulted points: 1.3b bs=12 (0.384-0.391
        # w/ stream_overlap), xl bs=12 (0.242-0.243). A lost ladder line
        # costs more than 0.01-0.03 MFU; BENCH_BS overrides for peak runs.
        # 2.7b/6.7b unmeasured: conservative bs=8.
        default_bs = {"gpt2-1.3b": 12, "gpt2-xl": 12,
                      "llama3.2-1b": 12}.get(model_name, 8)
    per_chip_bs = int(os.environ.get("BENCH_BS", default_bs))
    if bert:
        # the canonical BERT max_predictions_per_seq (80 at seq=512); the
        # synthetic batch is generated with the same cap so no label is ever
        # dropped by the gather (loss stays exact)
        maxp = int(math.ceil(0.15 * seq) + 3)
        # full-sequence flash tile: the bidirectional grid has no triangular
        # skip, so one 512-wide tile removes the tiling overhead entirely
        fb = int(os.environ.get("BENCH_FLASH_BLOCK", min(seq, 512)))
        config = dataclasses.replace(
            config,
            scan_unroll=int(os.environ.get("BENCH_UNROLL", config.n_layer)),
            max_predictions_per_seq=maxp,
            flash_block=fb or None,
            use_flash_attention=os.environ.get("BENCH_FLASH", "1") != "0")
        make_batch = partial(make_batch, max_predictions=maxp)
    elif (not model_name.startswith("llama") and not big
          and seq >= 1024 and on_tpu):
        # flash tile = the full 1024 sequence: one k-block per row — measured
        # 0.5012 → 0.5117 MFU on gpt2-760m v5e (256 tiles regress to 0.43).
        # Scoped to the measured headline class; the offload-backed ladder
        # models and llama keep the kernel default until measured.
        fb = int(os.environ.get("BENCH_FLASH_BLOCK", 1024))
        config = dataclasses.replace(config, flash_block=fb or None,
                                     scan_unroll=int(os.environ.get(
                                         "BENCH_UNROLL", 1)))
    # offload-backed models: fewer timed steps (each is ~45s of wall time at
    # gas=32 — two timed steps measure ~790k tokens, noise ±2%, and the
    # regression guard re-measures a collapsed line), and large accumulation
    # — the way ZeRO-Offload is actually run: the 15G fp32 streamed Adam
    # pass amortizes over the accumulation window
    steps = int(os.environ.get("BENCH_STEPS",
                               (2 if big else 30) if on_tpu else 3))
    # bert: gas=4 amortizes the Adam HBM pass (12ms on 334M fp32 state)
    # over four 134ms microsteps — measured 0.443 → 0.464 MFU on v5e.
    # offload-backed models: gas=32 amortizes the ~32G/step host round-trip
    # of the streamed fp32 state over a GPT-2-paper-sized token batch
    # (8x32x1024 = 262k tokens) — measured 0.177 → 0.342 MFU on gpt2-1.3b
    default_gas = 1
    if on_tpu and bert:
        default_gas = 4
    elif on_tpu and big:
        # gpt2-xl: gas=32 reproducibly faults the TPU worker (48-layer scan x
        # 32-microbatch program); 16 is stable and still 0.147 → 0.21+ MFU
        default_gas = 16 if model_name == "gpt2-xl" else 32
    gas = int(os.environ.get("BENCH_GAS", default_gas))
    # >1.3B fp32 Adam state exceeds a 16G chip: stream it from host memory
    # (the reference's ZeRO-Offload role, measured ~1.6s/step on gpt2-760m)
    offload = os.environ.get("BENCH_OFFLOAD", "cpu" if (big and on_tpu) else "none")
    if offload not in ("none", "cpu"):
        raise ValueError(f"BENCH_OFFLOAD={offload!r} not in ('none', 'cpu')")
    batch_size = per_chip_bs * n_dev * gas

    zero_cfg = {"stage": 3 if n_dev > 1 else 1}
    if offload == "cpu":
        zero_cfg["offload_optimizer"] = {"device": "cpu"}
        if model_name == "gpt2-1.3b" and "DS_TPU_OFFLOAD_OVERLAP" not in os.environ:
            # double-buffered streaming: stable 0.384-0.388 (serial 0.368)
            # across repeat v5e runs. xl NOT included: overlap there
            # intermittently faults the worker or collapses 3x.
            zero_cfg["offload_optimizer"]["stream_overlap"] = True
    ds_config = {
        "train_batch_size": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": zero_cfg,
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    overlap_mode = os.environ.get("BENCH_OVERLAP", "")
    if overlap_mode and overlap_mode != "off":
        if overlap_mode not in ("overlapped", "serial"):
            raise ValueError(f"BENCH_OVERLAP={overlap_mode!r} not in "
                             "('overlapped', 'serial', 'off')")
        ds_config["overlap"] = {"schedule": overlap_mode}
    wire_mode = os.environ.get("BENCH_WIRE", "")
    if wire_mode:
        if wire_mode not in ("off", "qwz", "qwz+hpz", "full"):
            raise ValueError(f"BENCH_WIRE={wire_mode!r} not in "
                             "('off', 'qwz', 'qwz+hpz', 'full')")
        # one mesh identity for the whole on/off pair: every wire mode —
        # including "off" — factors the data axis into (hosts × ici), so
        # ds_perf compares entries laid out identically and the xray comm
        # model can split intra-/inter-host bytes on BOTH sides
        ici = int(os.environ.get("BENCH_WIRE_ICI", 0)) or (
            n_dev // 2 if n_dev >= 4 and n_dev % 2 == 0 else 1)
        if ici > 1:
            ds_config["tpu"] = {"data": -1, "ici": ici}
        # EVERY wire mode — including "off" — arms the same overlap
        # schedule: the quantized gather rides the overlap engine's
        # prefetched scan, and the off side must compile the SAME
        # restructured program so the static_comm_bytes delta measures the
        # quantization alone, not overlap-vs-no-overlap
        ds_config.setdefault("overlap", {})
        if wire_mode != "off":
            wire_block = {"weight_quant_bits": 8}
            if wire_mode in ("qwz+hpz", "full"):
                if ici > 1:
                    wire_block["secondary_partition"] = True
                    wire_block["secondary_size"] = ici
                else:
                    # NO engine-side auto-factoring either: the off side
                    # runs flat, so hpZ must not silently change the mesh
                    # identity of the pair — it just degrades to qwZ here
                    print(f"# wire={wire_mode}: no intra-host split at "
                          f"{n_dev} device(s) (BENCH_WIRE_ICI) — hpZ "
                          "inactive, running qwZ only", file=sys.stderr)
            if wire_mode == "full":
                wire_block["grad_quant_bits"] = 4
            ds_config["wire"] = wire_block
    sdc_on = os.environ.get("BENCH_SDC", "0") == "1"
    sdc_interval = int(os.environ.get("BENCH_SDC_INTERVAL", 2))
    if sdc_on:
        # ds_sentry: replay audits + in-step checksum; the goodput ledger
        # below prices the audits into their own badput bucket, and the
        # recorded entry asserts the overhead stays under the
        # audit_interval^-1 budget (the sdc contract ds_perf gate holds)
        ds_config["sdc"] = {"audit_interval": sdc_interval}
    gray_on = os.environ.get("BENCH_GRAY", "0") == "1"
    gray_every = int(os.environ.get("BENCH_GRAY_EVERY", 2))
    if gray_on:
        # ds_gray in pricing mode: unconditional probes every gray_every
        # steps so the timed window deterministically holds probe badput;
        # probe_confirmations is set out of reach — the bench prices the
        # defense, it must never verdict/evict on CPU-sim probe noise
        ds_config["gray"] = {"probe_every": gray_every,
                             "probe_confirmations": 1_000_000,
                             "evict": False}
    blackbox_on = os.environ.get("BENCH_BLACKBOX", "0") == "1" and PERF
    if blackbox_on:
        # ds_blackbox: the always-on flight recorder — no chaos, no
        # triggers expected on a clean bench; the block is armed purely
        # so the entry PRICES the per-step ring cost (blackbox_overhead)
        # and the clean run proves zero bundles. Needs the PERF telemetry
        # session for its output dir, hence the `and PERF` gate above.
        ds_config["blackbox"] = {}
    if gas > 1:
        # bf16 accumulator: gas>1 must not add a resident fp32 grad tree on
        # top of the full optimizer state (16G HBM budget)
        ds_config["data_types"] = {"grad_accum_dtype": os.environ.get(
            "BENCH_ACC_DTYPE", "bf16")}
    if PERF:
        # telemetry session per line (own output dir: the failure record
        # points at it), census sampled at step 1 only (the record-time
        # census covers steady state; per-step walks stay off the timed
        # window), exporters flushed once at record time / exit. The
        # per-step sync telemetry adds is measured in docs/CONFIG.md
        # ("zero-overhead-when-off" table) and guarded by the EXPECTED
        # regression ledger like every other perturbation. The per-call
        # sequence number keeps an in-process retry (the headline
        # regression guard re-measures in the SAME process) from
        # overwriting the artifacts the first attempt's ledger entry
        # points at.
        global _RUN_SEQ
        _RUN_SEQ += 1
        tel_dir = os.path.join(TELEMETRY_ROOT,
                               f"{model_name}.{os.getpid()}.{_RUN_SEQ}")
        ds_config["telemetry"] = {
            "enabled": True, "output_dir": tel_dir, "prometheus": False,
            "flush_interval": 1_000_000}
        ds_config["profiling"] = {"sample_interval": 1_000_000}
        ds_config["perf"] = {"ledger_path": LEDGER}
        # per-step goodput/badput ledger: every ledger entry carries the
        # breakdown (compute / compile / exposed comm / data wait / ...)
        # of its own timed window, and ds_perf gate gates the resulting
        # goodput_fraction alongside the headline
        ds_config["goodput"] = {}
        # analytic roofline of the compiled step: every entry hoists
        # mfu_ceiling + mfu_gap (= ceiling − measured), the number
        # `ds_perf gate --metric mfu_gap` regresses on. One memoized AOT
        # compile per program — same cost shape as perf.static_comm.
        ds_config["roofline"] = {}
    if SMOKE:
        # the CPU dry run also drives the rewind ladder's tier-0 ring
        # (snapshots every step at this size), so a broken snapshot path
        # fails the smoke instead of the next real preemption
        ds_config["rewind"] = {"ram_interval": 1, "keep": 1}

    model = model_cls(config)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config)
    batch = make_batch(batch_size, seq, config.vocab_size, seed=0)
    batch = engine._shard_batch(batch)  # pre-place once; steps then pipeline

    # warmup / compile: two warm steps ALWAYS — measured (r5): charging the
    # first post-compile offload step to the timed window costs ~17% of the
    # xl line (pinned-host buffer setup rides step 1); the ladder budget cut
    # comes from steps 3->2 instead
    for _ in range(2):
        loss = engine.train_batch(batch)
    float(loss)  # host read = real completion barrier

    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    float(loss)
    dt = time.time() - t0

    tokens = batch_size * seq * steps
    tok_per_sec = tokens / dt
    tok_per_sec_chip = tok_per_sec / n_dev
    flops_per_token = config.flops_per_token(seq)
    achieved = tok_per_sec_chip * flops_per_token
    peak = get_accelerator().peak_flops()
    mfu = achieved / peak

    final_loss = float(loss)
    off_tag = f", offload={offload}" if offload != "none" else ""
    ov_tag = f", overlap={overlap_mode}" if overlap_mode else ""
    wire_tag = f", wire={wire_mode}" if wire_mode else ""
    sdc_tag = f", sdc@{sdc_interval}" if sdc_on else ""
    gray_tag = f", gray@{gray_every}" if gray_on else ""
    line = {
        "metric": f"{model_name} pretrain MFU (bs={per_chip_bs}/chip, seq={seq}, "
                  f"{n_dev} chip(s), gas={gas}{off_tag}{ov_tag}{wire_tag}{sdc_tag}{gray_tag}, "
                  f"tok/s/chip={tok_per_sec_chip:.0f}, "
                  f"TFLOPs/chip={achieved/1e12:.1f}, loss={final_loss:.3f})",
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / 0.50, 4),
    }
    if PERF:
        # the printed line BECOMES the ledger entry: legacy keys up front,
        # then identity fields + telemetry attribution (span p50/p99,
        # census buckets, compiled-step accounting, flops, exposed comm)
        # collected while the engine state is still alive
        try:
            line = engine.perf_record(
                line["metric"], line["value"], line["unit"],
                model=model_name, seed=0, timed_steps=steps,
                config={"bs_per_chip": per_chip_bs, "seq": seq, "gas": gas,
                        "remat": remat, "offload": offload, "n_dev": n_dev,
                        "steps": steps, "batch_size": batch_size,
                        "n_head": config.n_head,
                        "overlap": overlap_mode or None,
                        "wire": wire_mode or None,
                        "sdc": sdc_interval if sdc_on else None,
                        "gray": gray_every if gray_on else None,
                        "blackbox": blackbox_on or None,
                        "flash_block": getattr(config, "flash_block", None)},
                extra={"vs_baseline": line["vs_baseline"],
                       "tok_per_sec_chip": round(tok_per_sec_chip, 1),
                       "loss": round(final_loss, 4)})
            from deepspeed_tpu import telemetry as _tel

            _tel.flush()
            gp = (line.get("attribution") or {}).get("goodput") or {}
            if gp.get("goodput_fraction") is not None:
                total = sum(gp.get("buckets_us", {}).values()) or 1.0
                top = max(((b, v) for b, v in gp["buckets_us"].items()
                           if b != "compute"), key=lambda kv: kv[1],
                          default=None)
                note = (f"# goodput: {100.0 * gp['goodput_fraction']:.1f}% "
                        f"compute over {len(gp.get('per_step', []))} timed "
                        "step(s)")
                if top is not None:
                    note += (f"; top badput: {top[0]} "
                             f"{100.0 * top[1] / total:.1f}%")
                print(note, file=sys.stderr)
        except Exception as e:
            print(f"# perf record failed: {e}", file=sys.stderr)
        if sdc_on:
            # the sdc acceptance — OUTSIDE the best-effort try above: a
            # missing audit bucket must FAIL the bench, not print a note.
            # The entry must PRICE the defense: an `audit` goodput bucket
            # over the timed window and an sdc_overhead attribution under
            # the audit_interval^-1 budget (each audit replays ~one step
            # per interval, so the fraction sits near 1/(interval+1)
            # with headroom).
            att = line.get("attribution") or {}
            so = att.get("sdc_overhead")
            assert so is not None, (
                "sdc armed but the ledger entry carries no sdc_overhead "
                "attribution (goodput block missing, or perf_record "
                "failed above)")
            gp = att.get("goodput") or {}
            assert gp.get("buckets_us", {}).get("audit", 0.0) > 0.0, \
                "sdc armed but no audit bucket landed in the timed window"
            budget = 1.0 / max(1, sdc_interval)
            assert so < budget, (
                f"sdc_overhead {so:.3f} exceeds the audit_interval^-1 "
                f"budget {budget:.3f} — audits cost more wall than the "
                "sdc contract allows")
            print(f"# sdc: audit overhead {100.0 * so:.1f}% of wall "
                  f"(budget {100.0 * budget:.0f}%)", file=sys.stderr)
        if gray_on:
            # the gray acceptance — OUTSIDE the best-effort try above: a
            # missing probe bucket must FAIL the bench, not print a note.
            # The entry must PRICE the defense: a `probe` goodput bucket
            # over the timed window and a gray_overhead attribution under
            # the 2%-of-wall contract the subsystem self-gates on.
            att = line.get("attribution") or {}
            go = att.get("gray_overhead")
            assert go is not None, (
                "gray armed but the ledger entry carries no gray_overhead "
                "attribution (goodput block missing, or perf_record "
                "failed above)")
            gp = att.get("goodput") or {}
            assert gp.get("buckets_us", {}).get("probe", 0.0) > 0.0, \
                "gray armed but no probe bucket landed in the timed window"
            # the contract is <= 2% of wall at the DEFAULT cadence (a
            # suspicion-gated probe at most every probe_interval=10
            # steps); the bench forces probe_every=gray_every for
            # deterministic pricing, so scale the budget by the cadence
            # ratio — same per-probe cost, more probes per wall
            budget = 0.02 * (10.0 / max(1, gray_every))
            assert go < budget, (
                f"gray_overhead {go:.4f} exceeds {budget:.3f} "
                f"(2%-of-wall contract scaled from probe_interval=10 to "
                f"probe_every={gray_every}) — microprobes cost more than "
                "the ds_gray contract allows")
            print(f"# gray: probe overhead {100.0 * go:.2f}% of wall "
                  f"(budget {100.0 * budget:.1f}% at probe_every="
                  f"{gray_every})", file=sys.stderr)
        if blackbox_on:
            # the blackbox acceptance — OUTSIDE the best-effort try
            # above: a missing attribution must FAIL the bench, not
            # print a note. The entry must PRICE the always-on flight
            # recorder: a blackbox_overhead attribution under the
            # 0.5%-of-wall contract (`ds_perf gate --metric
            # blackbox_overhead` regresses on it), and a clean run must
            # write ZERO incident bundles.
            att = line.get("attribution") or {}
            bo = att.get("blackbox_overhead")
            assert bo is not None, (
                "blackbox armed but the ledger entry carries no "
                "blackbox_overhead attribution (telemetry/goodput "
                "missing, or perf_record failed above)")
            budget = 0.005
            assert bo < budget, (
                f"blackbox_overhead {bo:.5f} exceeds the {budget:.3f} "
                "(0.5%-of-wall) budget — the always-on flight recorder "
                "costs more than the ds_blackbox contract allows")
            rec = getattr(engine, "_blackbox", None)
            assert rec is not None and rec.bundles_written == 0, (
                "clean bench run wrote incident bundle(s) — a "
                "severity>=error event fired with no fault injected")
            print(f"# blackbox: recorder overhead {100.0 * bo:.3f}% of "
                  f"wall (budget {100.0 * budget:.1f}%), 0 bundles",
                  file=sys.stderr)

    # free this preset's device memory before the next ladder entry (the
    # north-star evidence step otherwise inherits a chip full of dead
    # buffers pinned by compiled-program constants and OOMs)
    _release(engine)
    return line


def serving_line(on_tpu: bool, n_dev: int) -> dict:
    """Measured serving decode throughput (BENCH_SERVE=1): init_inference
    on the headline model, batched greedy generate, report decode tok/s and
    MBU (model-bandwidth utilization — batched decode is HBM-bound: every
    generated token streams the weights once plus the live KV cache, so
    MBU = that traffic over peak bandwidth; the serving analogue of MFU).
    Prefill is measured separately (a max_new_tokens=1 call) and subtracted,
    so the line reports pure decode."""
    import dataclasses

    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models.registry import resolve_family

    name = os.environ.get("BENCH_MODEL", "gpt2-760m")
    model_cls, _, PRESETS = resolve_family(name)
    config = PRESETS[name]
    if not name.startswith("llama") and on_tpu:
        # decode wants the 128-aligned layout, NOT the fat-head training
        # relayout: measured 760m decode 6.4k tok/s at 12x128 vs 4.8k at
        # 4x384 (fewer heads under-fill the per-head decode grid while the
        # streamed bytes stay identical). Training and serving optima
        # genuinely differ — this line serves mxu_aligned and says so.
        # Relayout is also a bench-only liberty: a REAL trained checkpoint
        # must be served with its own head grouping (the grouping changes
        # outputs, not just speed), so canonical-when-unalignable (e.g.
        # gpt2-xl's 25x64 — xl decode layouts are unmeasured) is the
        # correctness-preserving default here.
        from deepspeed_tpu.models.registry import mxu_aligned

        config = mxu_aligned(config)
    B = int(os.environ.get("BENCH_BS", 32))
    prompt = int(os.environ.get("BENCH_SEQ", 128))
    gen = int(os.environ.get("BENCH_GEN", 128))
    if gen < 2:
        raise ValueError("BENCH_GEN must be >= 2 (prefill is solved out of "
                         "the two-point measurement)")

    model = model_cls(config)
    params = model.init_params(jax.random.PRNGKey(0))
    serve_dtype = os.environ.get("BENCH_SERVE_DTYPE", "bfloat16")
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": serve_dtype,
                       "max_out_tokens": prompt + gen}, params=params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config.vocab_size, (B, prompt), dtype=np.int32)
    reps = int(os.environ.get("BENCH_STEPS", 3 if on_tpu else 1))

    def timed(new_tokens):
        np.asarray(engine.generate(ids, max_new_tokens=new_tokens))  # compile
        t0 = time.time()
        for _ in range(reps):
            out = engine.generate(ids, max_new_tokens=new_tokens)
        np.asarray(out)  # host read = completion barrier
        return (time.time() - t0) / reps

    t_pre1 = timed(1)            # prefill + one decode step
    t_full = timed(gen)          # prefill + gen decode steps
    t_step = max(t_full - t_pre1, 1e-9) / (gen - 1)
    tok_s = B / t_step / n_dev
    # per-chip traffic per decode step: weights once (at the served width)
    # plus the live KV cache (k+v, all layers, padded length, at the CACHE
    # dtype — it follows the model config's dtype, not BENCH_SERVE_DTYPE)
    import jax.numpy as jnp

    dtype_bytes = {"float32": 4, "fp32": 4, "bfloat16": 2, "bf16": 2,
                   "float16": 2, "fp16": 2, "int8": 1}.get(serve_dtype, 2)
    param_bytes = config.num_params() * dtype_bytes
    kv_heads = getattr(config, "n_kv_head", None) or config.n_head
    kv_bytes = 2 * config.n_layer * B * (prompt + gen) * kv_heads * \
        config.head_dim * jnp.dtype(config.dtype).itemsize
    bw = get_accelerator().memory_bandwidth()
    mbu = (param_bytes + kv_bytes) / n_dev / (bw * t_step)
    line = {
        "metric": f"{name} serving decode (B={B}, prompt={prompt}, gen={gen}, "
                  f"{n_dev} chip(s), {serve_dtype}, tok/s/chip={tok_s:.0f}, "
                  f"prefill={t_pre1*1e3:.0f}ms, decode MBU={mbu:.3f})",
        "value": round(tok_s, 1),
        "unit": "decode-tok/s/chip",
        "vs_baseline": round(mbu, 4),
    }
    if PERF:
        # analytic MBU ceiling of this decode step: the bandwidth-bound
        # roofline model sized from the SAME KV-census bytes the measured
        # MBU credits (weights once + live KV per tick), capped by the
        # chip's compute axis at this batch. mbu_gap = ceiling − measured
        # is the decode line's roofline attribution (ROADMAP Item 5's
        # 0.674 debt finally has a ceiling to gap against).
        try:
            from deepspeed_tpu.analysis import chips as _chips
            from deepspeed_tpu.analysis.roofline import decode_mbu_ceiling

            dev = jax.local_devices()[0]
            chip = _chips.detect_chip_name(
                getattr(dev, "device_kind", ""), dev.platform)
            mbu_ceiling = decode_mbu_ceiling(
                (param_bytes + kv_bytes) / n_dev,
                flops=2.0 * config.num_params() * B / n_dev, chip=chip)
            line["mbu"] = round(mbu, 4)
            line["mbu_ceiling"] = round(mbu_ceiling, 4)
            line["mbu_gap"] = round(max(0.0, mbu_ceiling - mbu), 4)
        except Exception as e:
            print(f"# decode roofline failed: {e}", file=sys.stderr)
    return _structured(line, model=name,
                       config={"B": B, "prompt": prompt, "gen": gen,
                               "dtype": serve_dtype, "n_dev": n_dev})


def rlhf_line(on_tpu: bool, n_dev: int) -> dict:
    """Hybrid-engine RLHF actor evidence (the reference's flagship workload,
    blogs/deepspeed-chat/README.md:30 — OPT-13B step-3 in 9h on 8xA100):
    alternate ``generate`` (experience collection) and ``train_batch``
    (policy update) over the SAME live params and measure both phases.

    value = experience tok/s/chip END-TO-END (response tokens generated AND
    trained per wall second — the number that bounds RLHF step-3 wall time).
    vs_baseline = alternation efficiency (phase-sum / end-to-end wall): the
    hybrid engine's design claim is a zero-cost train<->generate flip (no
    module rewrite, no gather/scatter — runtime/hybrid_engine.py docstring),
    so this should sit at ~1.0.
    """
    import deepspeed_tpu
    from deepspeed_tpu.models.registry import resolve_family, tpu_native_layout

    name = os.environ.get("BENCH_MODEL", "gpt2-125m")
    model_cls, _, PRESETS = resolve_family(name)
    config = PRESETS[name]
    if not name.startswith("llama") and on_tpu:
        # same llama/GQA exclusion as every other consumer: kv_dim follows
        # n_kv_head, so the relayout is not param-invariant there
        config = tpu_native_layout(config, name)
    B = int(os.environ.get("BENCH_BS", 32))
    prompt = int(os.environ.get("BENCH_SEQ", 128))
    gen = int(os.environ.get("BENCH_GEN", 128))
    model = model_cls(config)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": B * n_dev,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-5}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "hybrid_engine": {"enabled": True, "max_out_tokens": prompt + gen},
        "steps_per_print": 0})
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, config.vocab_size, (B * n_dev, prompt),
                           dtype=np.int32)

    def one_iter():
        t0 = time.time()
        seqs = np.asarray(engine.generate(prompts, max_new_tokens=gen))
        t_gen = time.time() - t0
        mask = np.zeros(seqs.shape, np.float32)
        mask[:, prompt:] = 1.0          # train on the response tokens only
        t0 = time.time()
        loss = engine.train_batch({"input_ids": seqs.astype(np.int32),
                                   "loss_mask": mask})
        float(loss)
        return t_gen, time.time() - t0

    # TWO warm iterations: iter 0 compiles both phases against the freshly
    # initialized state's layouts; the donated step returns arrays whose
    # XLA-chosen layouts differ, so iter 1 recompiles BOTH programs once
    # more (measured: 5.3s+9.8s then 4.0s+8.6s, steady 0.39s+0.19s after)
    for _ in range(2):
        one_iter()
    iters = int(os.environ.get("BENCH_STEPS", 3))
    t0 = time.time()
    phases = [one_iter() for _ in range(iters)]
    e2e = (time.time() - t0) / iters
    t_gen = sum(p[0] for p in phases) / iters
    t_train = sum(p[1] for p in phases) / iters
    tok_s = B * gen / e2e
    return _structured({
        "metric": f"{name} rlhf actor alternation (B={B}/chip, prompt={prompt}, "
                  f"gen={gen}, {n_dev} chip(s), gen tok/s/chip={B*gen/t_gen:.0f}, "
                  f"train tok/s/chip={B*(prompt+gen)/t_train:.0f}, "
                  f"iter={e2e*1e3:.0f}ms)",
        "value": round(tok_s, 1),
        "unit": "rlhf-tok/s/chip",
        "vs_baseline": round((t_gen + t_train) / e2e, 4),
    }, model=name, config={"B": B, "prompt": prompt, "gen": gen,
                           "n_dev": n_dev})


def northstar_evidence(on_tpu: bool, n_dev: int) -> dict:
    """v5e-64 ZeRO-3 north-star projection from three MEASURED terms
    (profiling/scaling.py project_northstar):

    1. the per-chip microbatch (fwd+bwd) at the 64-chip compute regime —
       fp32 state dp-sharded into HBM, so no host streaming; measured as a
       grad-only step at the offload-free sweet spot (bs=14, remat='attn',
       loss-chunk residuals kept) on the TPU-native xl head layout (5x320,
       registry.TPU_HEAD_OVERRIDES — canonical 25x64 measures 0.429 in the
       same probe; both are in the r5 sweep table in this docstring);
    2. the per-step sharded Adam update on this chip's 1/64 state shard —
       the term the r4 grad-only proxy silently excluded; it is serial with
       the step (runs after the last grad), so the projection charges it
       at every overlap level;
    3. the ICI collective bytes (2 param all-gathers + 1 grad
       reduce-scatter, bf16) over the public per-chip ring bandwidth.

    The r4 offload-regime gas-solve breakdown (t_update 21.8s/step on one
    16G chip — why the offload ladder needs gas=16..32) was documentary,
    cost ~3 min of ladder budget, and is superseded by the ladder's three
    offload lines; it was dropped to fit the driver's bench window.
    """
    import dataclasses

    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models.gpt2 import GPT2Model, PRESETS, synthetic_lm_batch
    from deepspeed_tpu.models.registry import tpu_native_layout
    from deepspeed_tpu.profiling.scaling import project_northstar

    n_chips = int(os.environ.get("BENCH_NORTHSTAR_CHIPS", 64))
    gas = int(os.environ.get("BENCH_NORTHSTAR_GAS", 16))
    bs64 = int(os.environ.get("BENCH_NORTHSTAR_BS", 14))
    seq = 1024
    peak = get_accelerator().peak_flops()

    base = PRESETS["gpt2-xl"]
    fpt = base.flops_per_token(seq)
    cfg64 = dataclasses.replace(
        tpu_native_layout(base, "gpt2-xl"),
        remat="attn", flash_block=None, remat_loss_chunks=False)
    model64 = GPT2Model(cfg64)
    params64 = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                            model64.init_params(jax.random.PRNGKey(0)))
    ids64 = jnp.asarray(synthetic_lm_batch(
        bs64, seq, cfg64.vocab_size, seed=0)["input_ids"])
    # single-chip measurement program: placement is wherever the operands
    # live, stated explicitly (INHERIT) so the sharding lint can see it
    from deepspeed_tpu.sharding import INHERIT, sharded_jit

    grad_fn = sharded_jit(
        jax.grad(lambda p, i: model64.loss(p, {"input_ids": i})),
        label="bench/northstar_grad", donate_argnums=(),
        in_shardings=INHERIT, out_shardings=INHERIT)
    drain = lambda r: float(jnp.asarray(jax.tree.leaves(r)[0]).ravel()[0])
    drain(grad_fn(params64, ids64))          # compile
    # host contention only ever INFLATES wall time, so take the best of two
    # timed windows
    t_micro64 = float("inf")
    for _ in range(2):
        t0 = time.time()
        for _ in range(3):
            g = grad_fn(params64, ids64)
        drain(g)
        t_micro64 = min(t_micro64, (time.time() - t0) / 3)
    compute_mfu64 = (bs64 * seq / t_micro64) * fpt / peak
    del params64, g
    jax.clear_caches()

    # (2) the sharded optimizer update: fp32 AdamW on n_params/n_chips
    # elements, measured as one fused jit (the same leaf-update math the
    # engine compiles; HBM-bound: ~7 fp32 streams over the shard)
    import optax

    shard = int(base.num_params() // n_chips)
    opt = optax.adamw(1e-4, weight_decay=0.01)
    w = jnp.zeros((shard,), jnp.float32)
    gr = jnp.ones((shard,), jnp.float32) * 1e-3
    st = opt.init(w)

    reps = 20

    @partial(sharded_jit, label="bench/northstar_opt_update",
             donate_argnums=(), in_shardings=INHERIT, out_shardings=INHERIT)
    def upd_loop(w, st, gr):
        # lax.scan inside ONE jit: a ~1ms HBM-bound update is the same
        # order as one host dispatch on a local chip, so timing it per call
        # would measure the enqueue, not the update
        def body(carry, _):
            w, st = carry
            u, st = opt.update(gr, st, w)
            return (optax.apply_updates(w, u), st), None

        (w, st), _ = jax.lax.scan(body, (w, st), None, length=reps)
        return w, st

    w2, st2 = upd_loop(w, st, gr)
    float(w2[0])                              # compile + barrier
    t0 = time.time()
    w2, st2 = upd_loop(w2, st2, gr)
    float(w2[0])
    t_update_shard = (time.time() - t0) / reps
    del w, w2, st, st2, gr
    jax.clear_caches()

    proj = project_northstar(
        n_params=base.num_params(),
        tokens_per_chip_step=bs64 * seq * gas,
        flops_per_token=fpt,
        measured_mfu_1chip=compute_mfu64,     # raises if out of (0,1)
        peak_flops=peak,
        n_chips=n_chips,
        t_update_shard_s=t_update_shard)
    return _structured({
        "metric": f"gpt2-xl v5e-{n_chips} ZeRO-3 north-star projection "
                  f"(measured compute regime @bs={bs64} heads="
                  f"{cfg64.n_head}x{cfg64.n_embd // cfg64.n_head}: "
                  f"t_micro={t_micro64*1e3:.0f}ms MFU={compute_mfu64:.3f}; "
                  f"measured 1/{n_chips}-shard Adam update="
                  f"{t_update_shard*1e3:.1f}ms/step; gas={gas}; "
                  f"projected MFU no/mid/full overlap="
                  f"{proj['projected_mfu_no_overlap']}/"
                  f"{proj['projected_mfu_mid_overlap']}/"
                  f"{proj['projected_mfu_full_overlap']}; "
                  f"{proj['assumptions']})",
        "value": proj["projected_mfu_mid_overlap"],
        "unit": "projected-MFU",
        "vs_baseline": round(proj["projected_mfu_mid_overlap"] / 0.50, 4),
    }, model="gpt2-xl", config={"n_chips": n_chips, "gas": gas, "bs": bs64,
                                "t_update_shard_ms":
                                    round(t_update_shard * 1e3, 2)})


def _canonical_series(label, unit):
    """The series name the SUCCESS line of this ladder slot carries
    (metric string before the knob parenthesis) — stamped onto fail/skip
    lines as the explicit ``series`` field so `ds_perf gate` sees a
    crashed benchmark as the same series it failed to measure, not as a
    disjoint 'X FAILED' series a stale success could hide behind."""
    if unit == "decode-tok/s/chip":
        return f"{os.environ.get('BENCH_MODEL', 'gpt2-760m')} serving decode"
    if unit == "rlhf-tok/s/chip":
        return (f"{os.environ.get('BENCH_MODEL', 'gpt2-125m')} "
                f"rlhf actor alternation")
    if unit == "projected-MFU":
        chips = os.environ.get("BENCH_NORTHSTAR_CHIPS", "64")
        return f"gpt2-xl v5e-{chips} ZeRO-3 north-star projection"
    # MFU ladder labels are model names, except the seq-variant bert line
    # ("bert-large seq128 record config") which shares bert-large's series
    return f"{label.split(' seq', 1)[0]} pretrain MFU"


def _fail_line(name, e, unit="MFU"):
    """A failed ladder line, diagnosable from the ledger alone: exception
    type + message in the metric string (compat), full traceback and the
    line's telemetry session path in the structured record (the trace /
    metrics of the partial run are the first thing a post-mortem wants)."""
    import traceback

    line = {"metric": f"{name} FAILED: {type(e).__name__} {str(e)[:120]}",
            "value": 0.0, "unit": unit, "vs_baseline": 0.0,
            "series": _canonical_series(name, unit),
            "failed": True, "error_type": type(e).__name__,
            "traceback": "".join(traceback.format_exception(
                type(e), e, e.__traceback__))[-4000:]}
    try:
        from deepspeed_tpu import telemetry as _tel

        session = _tel.get_session()
        if session is not None:
            line["telemetry_dir"] = session.output_dir
            _tel.flush()     # land the partial run's spans/series for the
            # post-mortem — the session won't reach its exit flush if the
            # driver kills this process next
    except Exception:
        pass
    return _ledger_append(line)


# Per-line regression ledger (VERDICT r4 #10): the measured sweet-spot values
# this ladder is expected to reproduce (same source as the README perf
# table). A line under 85% of its entry carries "regression": true in the
# emitted JSON; under 70% it is re-measured once first (r4's llama line
# measured 0.136 vs 0.341 under the driver — an environmental collapse a
# single re-run catches).
EXPECTED = {
    "gpt2-760m": 0.565,           # 4x384 TPU-native layout (12x128: 0.536)
    "gpt2-xl": 0.25,              # 5x320 TPU-native layout (25x64: 0.247)
    "gpt2-1.3b": 0.383,
    "llama3.2-1b": 0.341,
    "bert-large": 0.573,          # 2x512 (8x128: 0.568)
    "bert-large seq128 record config": 0.69,   # 2x512 (8x128: 0.614)
    "gpt2-moe-125m": 0.398,
    "serving decode": 6300.0,
    "rlhf actor": 6800.0,
    "northstar projection": 0.49,
}

# Wall-clock estimates per ladder line (measured r5, includes subprocess
# start + compile), used to decide whether a line still fits the deadline.
ESTIMATE_S = {
    "gpt2-760m": 150, "gpt2-xl": 220, "gpt2-1.3b": 200, "llama3.2-1b": 220,
    "bert-large": 340, "bert-large seq128 record config": 240,
    "gpt2-moe-125m": 90, "serving decode": 100, "rlhf actor": 110,
    "northstar projection": 160,
}


def _subproc_line(env_overrides, name, unit="MFU", timeout_s=1500,
                  time_left=None):
    """Run one ladder entry in a SUBPROCESS and parse its JSON line.

    A TPU worker crash (observed on the offload-backed big models) kills
    the whole jax backend of the process it happens in — in-process ladder
    entries after it can only fail. Isolation caps the blast radius at one
    line. A chip belongs to one process at a time, so the children run one
    after another and the ladder parent never initialises a jax backend
    (see main()).
    """
    import subprocess

    def parse(stdout, stderr):
        # TimeoutExpired carries BYTES even under text=True (observed on
        # this Python 3.12) — normalize before parsing
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        for line in reversed((stdout or "").strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError(f"no metric line (stderr tail: "
                           f"{(stderr or '').strip()[-160:]})")

    env = dict(os.environ, BENCH_SUITE="0", **env_overrides)
    last = None
    for attempt in range(2):   # worker crashes are intermittent: retry once
        # every attempt is bounded by BOTH the per-line budget and the
        # ladder's remaining deadline — without the second bound, a hung
        # child + retry spends ~2x the budget and reproduces the r4 rc=124
        att_timeout = timeout_s
        if time_left is not None:
            att_timeout = min(att_timeout, time_left() - 10)
            if att_timeout < 45:
                return last or _fail_line(
                    name, TimeoutError("deadline exhausted before attempt"),
                    unit)
        t0 = time.time()
        try:
            out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                                 env=env, capture_output=True, text=True,
                                 timeout=att_timeout)
            if out.returncode == NO_CHIP_EXIT:
                sys.stderr.write(out.stderr[-400:])
                sys.exit(NO_CHIP_EXIT)
            return parse(out.stdout, out.stderr)
        except subprocess.TimeoutExpired as e:
            # a child can finish the measurement and then hang in TPU
            # runtime teardown — recover the already-printed line
            try:
                return parse(e.stdout, e.stderr)
            except Exception:
                last = _fail_line(name, e, unit)
        except Exception as e:
            last = _fail_line(name, e, unit)
        if time.time() - t0 > 300:
            # slow failure (hang/timeout, not a crash): a retry would burn
            # another full window for the same outcome — bound the ladder's
            # worst-case wall time instead
            break
        if attempt == 0:
            time.sleep(20)     # let a crashed TPU worker restart
    return last


# exit code of a measuring process that found no chip: the ladder parent
# stops on it at once instead of retrying what cannot succeed
NO_CHIP_EXIT = 78


def _require_chip():
    """The first jax touch of a measuring process. A CPU run is not a
    benchmark: without a TPU only the dry runs (--smoke, --devices N) go
    on; anything else exits non-zero instead of benching a toy on the CPU."""
    if SMOKE or _devices > 1:
        return
    if jax.default_backend() != "tpu":
        print(f"bench.py: no TPU (jax.default_backend() is "
              f"{jax.default_backend()!r}). Send the bench through the chip "
              "tool; --smoke / --devices N are the CPU dry runs.",
              file=sys.stderr)
        sys.exit(NO_CHIP_EXIT)


def main():
    t_start = time.time()
    special = next((fn for var, fn in (("BENCH_NORTHSTAR", northstar_evidence),
                                       ("BENCH_SERVE", serving_line),
                                       ("BENCH_RLHF", rlhf_line))
                    if os.environ.get(var) == "1"), None)
    model_name = os.environ.get("BENCH_MODEL")
    dry_run = SMOKE or _devices > 1
    if special is not None or model_name is not None or dry_run \
            or os.environ.get("BENCH_SUITE", "1") == "0":
        # ONE line, measured in this process (a ladder child, or a user
        # benching one preset). A failure is a traceback and a non-zero
        # exit; the ladder parent turns that into the FAILED line.
        _require_chip()
        n_dev = len(jax.devices())
        on_tpu = jax.default_backend() == "tpu"
        if special is not None:
            line = special(on_tpu, n_dev)
        else:
            line = run_one(model_name or ("gpt2-tiny" if dry_run
                                          else "gpt2-760m"), on_tpu, n_dev)
        print(json.dumps(line), flush=True)
        return

    # THE LADDER. This parent never initialises a jax backend: a chip
    # belongs to one process at a time, so a parent that had touched jax
    # would hold the chip and every child would fail or hang. Every line —
    # the headline included — is a child, one at a time: headline FIRST (so
    # a driver timeout mid-ladder still leaves its line as the most recent
    # JSON), then the offload family (1.5B north star, 1.3B, llama3.2-1b
    # GQA/128k-vocab), BERT (the reference's record family, seq512 + its
    # published seq128 record config), MoE, serving decode, the v5e-64
    # projection, then the SAME headline line REPEATED last for the
    # tail-line parse.
    #
    # The whole ladder runs under a wall-clock deadline (BENCH_DEADLINE_S,
    # default 1620s): r4's ladder outran the driver's budget (BENCH_r04
    # rc=124) and the parsed metric was whatever line happened to be last.
    # Lines that no longer fit are SKIPPED (explicit skip line), the
    # headline always prints last, and SIGTERM/SIGINT re-print it before a
    # non-zero exit so even a hard timeout leaves the right tail line.
    deadline = float(os.environ.get("BENCH_DEADLINE_S", 1620))
    reserve = 25.0
    failed = []

    def remaining():
        return deadline - (time.time() - t_start)

    def guarded(label, env, unit="MFU"):
        """One ladder line under the deadline + regression guard."""
        est = ESTIMATE_S.get(label, 240)
        budget = remaining() - reserve
        if budget < min(0.7 * est, 150):
            return _ledger_append(
                {"metric": f"{label} SKIPPED (deadline "
                           f"{deadline:.0f}s, {budget:.0f}s left)",
                 "value": 0.0, "unit": unit, "vs_baseline": 0.0,
                 "series": _canonical_series(label, unit),
                 "skipped": True})
        time_left = lambda: remaining() - reserve
        line = _subproc_line(env, label, unit,
                             timeout_s=min(900, budget),
                             time_left=time_left)
        exp = EXPECTED.get(label)
        val = line.get("value") or 0.0
        if exp and val < 0.70 * exp and time_left() > 0.8 * est:
            # r4's llama collapse (0.136 vs 0.341) was environmental —
            # one fresh subprocess usually recovers the real number
            retry = _subproc_line(env, label, unit,
                                  timeout_s=min(900, time_left()),
                                  time_left=time_left)
            if (retry.get("value") or 0.0) > val:
                line = retry
                val = retry.get("value") or 0.0
            else:
                # the discarded retry (worse, or crashed) is now the
                # ledger's NEWEST entry of this series — re-append the
                # kept measurement so ds_perf gate/diff judge the line
                # the ladder actually reports
                line = _ledger_append(dict(line, kept_after_retry=True))
        if exp and val < 0.85 * exp:
            line["regression"] = True
            line["expected"] = exp
        if line.get("failed"):
            failed.append(label)
        return line

    # the headline is under the same regression guard as the suite lines
    # (it IS the line the driver records — an environmental collapse here
    # is the worst place to go undetected)
    headline = guarded("gpt2-760m", {"BENCH_MODEL": "gpt2-760m"})
    print(json.dumps(headline), flush=True)
    if failed:   # a dead headline is a dead bench (no chip, most likely)
        sys.exit(1)

    import signal

    tail = (json.dumps(headline) + "\n").encode()

    def _tail_headline(signum, frame):
        os.write(1, tail)            # pre-rendered: no json/stdio in a handler
        sys.exit(128 + signum)       # an interrupted ladder did not pass

    signal.signal(signal.SIGTERM, _tail_headline)
    signal.signal(signal.SIGINT, _tail_headline)

    for label, env in (
            ("gpt2-xl", {"BENCH_MODEL": "gpt2-xl"}),
            ("gpt2-1.3b", {"BENCH_MODEL": "gpt2-1.3b"}),
            ("llama3.2-1b", {"BENCH_MODEL": "llama3.2-1b"}),
            ("bert-large", {"BENCH_MODEL": "bert-large"}),
            # the reference's own record config (64 TFLOPS/V100 ~ 51% of
            # peak at seq=128, docs/_posts/2020-05-28): measured 0.61 here
            ("bert-large seq128 record config",
             {"BENCH_MODEL": "bert-large", "BENCH_SEQ": "128",
              "BENCH_GAS": "8"}),
            ("gpt2-moe-125m", {"BENCH_MODEL": "gpt2-moe-125m"})):
        print(json.dumps(guarded(label, env)), flush=True)
    if os.environ.get("BENCH_SERVE_LINE", "1") != "0":
        # serving evidence: batched decode tok/s + MBU on the headline
        # model (prefill solved out) — the inference-engine counterpart
        # of the training MFU lines
        print(json.dumps(guarded("serving decode", {"BENCH_SERVE": "1"},
                                 unit="decode-tok/s/chip")), flush=True)
    if os.environ.get("BENCH_RLHF_LINE", "1") != "0":
        # RLHF actor evidence (VERDICT r4 #4): the reference's flagship
        # DeepSpeed-Chat workload had zero perf lines until r5
        print(json.dumps(guarded("rlhf actor", {"BENCH_RLHF": "1"},
                                 unit="rlhf-tok/s/chip")), flush=True)
    if os.environ.get("BENCH_SCALING", "1") != "0":
        # scaling evidence for the v5e-64 north star (VERDICT r3 #10):
        # measured compute + sharded-update + ICI projection
        print(json.dumps(guarded("northstar projection",
                                 {"BENCH_NORTHSTAR": "1"},
                                 unit="projected-MFU")), flush=True)
    print(json.dumps(headline), flush=True)
    if failed:
        sys.exit(f"bench.py: {len(failed)} ladder line(s) FAILED: "
                 + ", ".join(failed))


if __name__ == "__main__":
    main()
