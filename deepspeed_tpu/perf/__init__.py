"""Perf ledger: telemetry-instrumented benchmarking + regression gates.

PRs 2-5 built the observability (telemetry histograms, HBM census, fleet
trace merge, critical-path extraction) — this package closes the loop by
making every performance NUMBER carry that attribution and every
regression fail loudly:

* :mod:`~deepspeed_tpu.perf.ledger` — append-only JSONL of structured
  benchmark entries (model/config/env/seed/git_rev as FIELDS, keyed by
  the PR 3 config/code fingerprint, per-step samples for noise bounds);
* :mod:`~deepspeed_tpu.perf.attribution` — fold the live telemetry
  session + profiling hooks into a per-entry breakdown (span p50/p99,
  memory buckets, flops, exposed-comm µs/step);
* :mod:`~deepspeed_tpu.perf.recorder` — the engine-side writer behind
  the ``perf`` ds_config block (STRICT no-op when the block is absent:
  this package is never imported — same contract as ``analysis`` and
  ``profiling``);
* :mod:`~deepspeed_tpu.perf.calibration` — predicted-vs-measured error
  over the autotuner's cost models;
* :mod:`~deepspeed_tpu.perf.cli` — ``bin/ds_perf`` (show / diff / gate /
  calibration), pure stdlib so it runs far from any TPU.

A training script records through ``engine.perf_record(...)``;
``ds_perf gate --baseline A.jsonl --candidate B.jsonl`` over two ledgers
written that way fails a change that regresses a gated series.
"""

from deepspeed_tpu.perf.ledger import (SCHEMA_VERSION, append_entry, compare,
                                       git_rev, latest_by_series,
                                       load_baseline, load_entries,
                                       series_key, welch_t)

__all__ = ["SCHEMA_VERSION", "append_entry", "compare", "git_rev",
           "latest_by_series", "load_baseline", "load_entries", "series_key",
           "welch_t"]
