"""Analytic roofline over the compiled HLO (``ds_roofline``).

Ten observability PRs can say where the wall-seconds WENT; this module
says how fast the program COULD have gone. It prices the same
post-GSPMD HLO text ds_xray already parses against a per-chip peak
table (:mod:`deepspeed_tpu.analysis.chips`):

* per region (dot / convolution / fusion / any costed instruction of a
  non-fused computation): analytic FLOPs and HBM bytes-accessed from
  :func:`hlo_model.parse_hlo_module`, predicted time
  ``max(flops/peak_flops, bytes/hbm_bw)``, and a compute- vs
  memory-bound verdict;
* per program: predicted step seconds (Σ region times — an OPTIMISTIC
  ceiling: perfect overlap of everything but the slower axis of each
  region, wire time not included), ``mfu_ceiling`` = total_flops /
  (peak × predicted), and the measured-vs-ceiling ``mfu_gap`` the perf
  ledger gates;
* for decode programs: a bandwidth-bound ``mbu_ceiling`` sized from the
  KV-census bytes (:func:`decode_mbu_ceiling`).

When jax is live the regex model is CROSS-CHECKED against
``compiled.cost_analysis()`` (both sides share the HloCostAnalysis
counting conventions — while bodies once, transcendentals separate —
so they agree within a few percent, asserted in tier-1). On a saved
``.hlo`` dump the regex model stands alone: this module imports with NO
jax at all, the same contract as ``bin/ds_prof``. Strict no-op: without
the ``roofline`` ds_config block this module is never imported
(asserted in tests).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any, Dict, List, Optional

from deepspeed_tpu.analysis import chips as _chips
from deepspeed_tpu.analysis.hlo_model import HloModel, parse_hlo_module

__all__ = ["RegionCost", "RooflineReport", "analyze_hlo_model",
           "analyze_hlo_text", "roofline_program", "roofline_for_engine",
           "engine_roofline_analysis", "decode_mbu_ceiling",
           "roofline_table_for_config", "roofline_cli"]

COMPUTE_BOUND = "compute"
MEMORY_BOUND = "memory"


@dataclasses.dataclass
class RegionCost:
    """One roofline region: an instruction priced on both axes."""

    name: str
    opcode: str
    computation: str
    flops: int
    bytes: int
    seconds: float            # max(flops/peak, bytes/bw)
    bound: str                # COMPUTE_BOUND | MEMORY_BOUND
    metadata_op: str = ""

    def intensity(self) -> float:
        """Arithmetic intensity, FLOPs per HBM byte."""
        return self.flops / self.bytes if self.bytes else float("inf")

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "opcode": self.opcode,
                "computation": self.computation, "flops": self.flops,
                "bytes": self.bytes, "seconds": self.seconds,
                "bound": self.bound, "metadata_op": self.metadata_op}


@dataclasses.dataclass
class RooflineReport:
    """The roofline verdict for ONE compiled program on ONE chip."""

    program: str
    chip: str
    num_partitions: int
    total_flops: int
    total_bytes: int
    transcendentals: int
    predicted_step_s: float
    mfu_ceiling: float
    regions: List[RegionCost]          # sorted by predicted time, desc
    # live cross-check (None on saved dumps / no-jax)
    xla_flops: Optional[float] = None
    xla_bytes: Optional[float] = None

    def flops_agreement(self) -> Optional[float]:
        """regex-model / cost_analysis flops ratio (1.0 = exact)."""
        if not self.xla_flops:
            return None
        return self.total_flops / self.xla_flops

    def memory_bound_share(self) -> float:
        """Fraction of predicted step time spent memory-bound."""
        if self.predicted_step_s <= 0:
            return 0.0
        mem = sum(r.seconds for r in self.regions if r.bound == MEMORY_BOUND)
        return mem / self.predicted_step_s

    def top_memory_bound(self) -> Optional[RegionCost]:
        """The single most expensive memory-bound region (the "what do I
        fuse/relayout next" answer)."""
        for r in self.regions:
            if r.bound == MEMORY_BOUND:
                return r
        return None

    def summary(self) -> Dict[str, Any]:
        """The compact dict perf attribution stamps into ledger entries."""
        out = {"program": self.program, "chip": self.chip,
               "predicted_step_us": round(1e6 * self.predicted_step_s, 1),
               "mfu_ceiling": round(self.mfu_ceiling, 4),
               "total_flops": self.total_flops,
               "total_bytes": self.total_bytes,
               "regions": len(self.regions),
               "memory_bound_share": round(self.memory_bound_share(), 4)}
        agree = self.flops_agreement()
        if agree is not None:
            out["flops_vs_xla"] = round(agree, 4)
        top = self.regions[0] if self.regions else None
        if top is not None:
            out["top_region"] = {
                "name": top.name, "opcode": top.opcode, "bound": top.bound,
                "share": round(top.seconds / self.predicted_step_s, 4)
                if self.predicted_step_s > 0 else 0.0}
        return out

    def to_dict(self, top_k: Optional[int] = None) -> Dict[str, Any]:
        d = self.summary()
        d["num_partitions"] = self.num_partitions
        d["transcendentals"] = self.transcendentals
        if self.xla_flops is not None:
            d["xla_flops"] = self.xla_flops
        if self.xla_bytes is not None:
            d["xla_bytes"] = self.xla_bytes
        d["top_regions"] = [r.to_dict()
                            for r in self.regions[:top_k or len(self.regions)]]
        return d

    def render(self, top_k: int = 8) -> str:
        """The per-program "top-K regions by predicted time" table."""
        spec = _chips.resolve_chip(self.chip)
        head = (f"roofline[{self.program or '?'}] chip={spec.name} "
                f"partitions={self.num_partitions} "
                f"predicted_step={_fmt_s(self.predicted_step_s)} "
                f"mfu_ceiling={self.mfu_ceiling:.3f} "
                f"mem-bound={self.memory_bound_share():.0%} of step")
        agree = self.flops_agreement()
        if agree is not None:
            head += f" (model/xla flops {agree:.3f})"
        lines = [head]
        lines.append(f"  {'region':34} {'op':12} {'time':>9} {'%step':>6} "
                     f"{'bound':>8} {'fl/B':>8}")
        for r in self.regions[:top_k]:
            share = (r.seconds / self.predicted_step_s
                     if self.predicted_step_s > 0 else 0.0)
            ai = r.intensity()
            lines.append(
                f"  %{r.name[:33]:33} {r.opcode[:12]:12} "
                f"{_fmt_s(r.seconds):>9} {share:>6.1%} {r.bound:>8} "
                f"{(f'{ai:.1f}' if ai != float('inf') else 'inf'):>8}")
        if len(self.regions) > top_k:
            rest = sum(r.seconds for r in self.regions[top_k:])
            lines.append(f"  (+{len(self.regions) - top_k} more regions, "
                         f"{_fmt_s(rest)})")
        return "\n".join(lines)


def _fmt_s(s: float) -> str:
    if s >= 1.0:
        return f"{s:.2f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f}ms"
    return f"{s * 1e6:.0f}us"


# ---------------------------------------------------------------- analysis
def analyze_hlo_model(model: HloModel, chip: str = "cpu-sim",
                      program: str = "",
                      dtype: Optional[str] = None) -> RooflineReport:
    """Price a parsed :class:`HloModel` against a chip's roofline."""
    spec = _chips.resolve_chip(chip)
    peak = spec.peak_flops_for(dtype)
    bw = spec.hbm_bytes_per_s
    regions: List[RegionCost] = []
    for op in model.compute_ops:
        t_comp = op.flops / peak if peak > 0 else 0.0
        t_mem = op.bytes / bw if bw > 0 else 0.0
        if t_comp <= 0 and t_mem <= 0:
            continue
        regions.append(RegionCost(
            name=op.name, opcode=op.opcode, computation=op.computation,
            flops=op.flops, bytes=op.bytes,
            seconds=max(t_comp, t_mem),
            bound=COMPUTE_BOUND if t_comp > t_mem else MEMORY_BOUND,
            metadata_op=op.metadata_op))
    regions.sort(key=lambda r: r.seconds, reverse=True)
    predicted = sum(r.seconds for r in regions)
    total_flops = model.total_flops()
    mfu = (total_flops / (peak * predicted)
           if predicted > 0 and peak > 0 else 0.0)
    return RooflineReport(
        program=program or model.name, chip=spec.name,
        num_partitions=model.num_partitions, total_flops=total_flops,
        total_bytes=model.total_bytes_accessed(),
        transcendentals=model.total_transcendentals(),
        predicted_step_s=predicted, mfu_ceiling=min(1.0, mfu),
        regions=regions)


def analyze_hlo_text(text: str, chip: str = "cpu-sim", program: str = "",
                     dtype: Optional[str] = None) -> RooflineReport:
    """Roofline of raw compiled-HLO text — works on a saved ``.hlo``
    dump with no jax in the process (the ``ds_prof`` contract)."""
    return analyze_hlo_model(parse_hlo_module(text), chip=chip,
                             program=program, dtype=dtype)


def decode_mbu_ceiling(useful_bytes: float, flops: float = 0.0,
                       chip: str = "cpu-sim",
                       overhead_bytes: float = 0.0) -> float:
    """Bandwidth-bound MBU ceiling of one decode step on one chip.

    ``useful_bytes`` is the per-chip traffic the MBU metric CREDITS —
    the KV-census number bench already measures (weights once + live KV
    per decode step). ``overhead_bytes`` is traffic the step pays but
    the metric does not credit (activations, collective staging);
    ``flops`` caps the ceiling when the step is compute-bound (fat
    batches). MBU ceiling = (useful/bw) / max(mem_time, compute_time),
    so with zero overhead and negligible flops the ceiling is 1.0."""
    spec = _chips.resolve_chip(chip)
    bw, peak = spec.hbm_bytes_per_s, spec.peak_flops
    if bw <= 0 or useful_bytes <= 0:
        return 0.0
    t_mem = (useful_bytes + max(0.0, overhead_bytes)) / bw
    t_comp = flops / peak if peak > 0 else 0.0
    t = max(t_mem, t_comp)
    if t <= 0:
        return 0.0
    return min(1.0, (useful_bytes / bw) / t)


# --------------------------------------------------------------- live paths
def chip_for_engine(engine) -> str:
    """The chip to price against: the config's explicit choice, else
    detected from the live device kind (``cpu-sim`` on CPU meshes; a
    device the peak table does not list raises)."""
    cfg = getattr(getattr(engine, "_config", None), "roofline", None)
    explicit = getattr(cfg, "chip", "") or ""
    if explicit and explicit != "auto":
        return _chips.resolve_chip(explicit).name
    import jax

    dev = jax.local_devices()[0]
    return _chips.detect_chip_name(dev.device_kind, dev.platform)


def roofline_program(record, chip: str = "cpu-sim") -> Optional[RooflineReport]:
    """AOT re-lower one :class:`ProgramRecord` (the ds_xray kit: same
    mesh context, same abstract args) and price it — with the
    ``cost_analysis()`` cross-check stamped in. None when the record
    cannot be lowered."""
    try:
        compiled = record.compiled()        # the door's one lower-and-compile
        if compiled is None:
            return None
        text = compiled.as_text()
    except Exception:
        return None
    rep = analyze_hlo_text(text, chip=chip, program=record.label)
    # ONE flops/bytes extraction helper shared with the flops profiler —
    # EstTFLOPs and mfu_ceiling can never disagree on the same program
    try:
        from deepspeed_tpu.profiling.flops_profiler.profiler import \
            extract_compiled_cost

        cost = extract_compiled_cost(compiled)
        rep.xla_flops = cost.get("flops") or None
        rep.xla_bytes = cost.get("bytes_accessed") or None
    except Exception:
        pass
    return rep


def roofline_for_engine(engine) -> Optional[RooflineReport]:
    """THIS engine's train program's roofline, for perf-ledger
    attribution — or None (the gate's exit-3 "missing" signal).

    Program matching mirrors ``xray.static_comm_for_engine``: newest
    ``engine/train_batch`` registration on this engine's mesh object,
    preferring its configured gas. Deterministic per compiled program,
    so memoized on the record — a loop recording N perf entries pays
    the AOT compile once."""
    from deepspeed_tpu.sharding import program_table

    mesh = getattr(engine, "mesh", None)
    gas = getattr(getattr(engine, "_config", None),
                  "gradient_accumulation_steps", None)
    candidates = [rec for rec in program_table().values()
                  if rec.label.startswith("engine/train_batch")
                  and rec.can_lower()]
    train = None
    for rec in reversed(candidates):
        if rec.mesh is not mesh:
            continue
        if gas is not None and f"[gas={gas}]" not in rec.label:
            train = train or rec
            continue
        train = rec
        break
    if train is None:
        return None
    chip = chip_for_engine(engine)
    cached = getattr(train, "_roofline_cache", None)
    if cached is not None and cached[0] == chip:
        return cached[1]
    rep = roofline_program(train, chip=chip)
    if rep is not None:
        train._roofline_cache = (chip, rep)
    return rep


# ------------------------------------------------------------- engine pass
def engine_roofline_analysis(engine):
    """The opt-in roofline pass, run once after the FIRST train_batch
    (the program table must hold compiled programs) — xray-style: every
    re-lowerable program in the PR-12 table is priced (one AOT compile
    each, memoized), the engine's own train program feeds the
    ``roofline/*`` gauges ds_top/ds_metrics render and the report the
    logs carry. Never raises into the step path."""
    from deepspeed_tpu import telemetry as _telemetry
    from deepspeed_tpu.sharding import program_table
    from deepspeed_tpu.utils.logging import log_dist, logger

    cfg = engine._config.roofline
    chip = chip_for_engine(engine)
    reports: List[RooflineReport] = []
    for rec in sorted(program_table().values(), key=lambda r: r.label):
        try:
            cached = getattr(rec, "_roofline_cache", None)
            rep = (cached[1] if cached is not None and cached[0] == chip
                   else roofline_program(rec, chip=chip))
            if rep is not None:
                rec._roofline_cache = (chip, rep)
                reports.append(rep)
        except Exception as e:  # pragma: no cover - analysis never fatal
            logger.warning(f"roofline: {rec.label!r} skipped: {e}")
    engine._roofline_reports = reports
    train = roofline_for_engine(engine)
    engine._roofline_result = train
    if train is not None:
        try:
            reg = _telemetry.get_registry()
            reg.gauge("roofline/mfu_ceiling").set(float(train.mfu_ceiling))
            reg.gauge("roofline/predicted_step_us").set(
                1e6 * train.predicted_step_s)
            reg.gauge("roofline/memory_bound_share").set(
                float(train.memory_bound_share()))
            agree = train.flops_agreement()
            if agree is not None:
                reg.gauge("roofline/flops_vs_xla").set(float(agree))
        except Exception:
            pass
    body = "\n".join(r.render(top_k=int(getattr(cfg, "top_k", 8)))
                     for r in reports) or \
        "roofline: no re-lowerable programs in the table"
    log_dist(f"ds_roofline report ({len(reports)} program(s))\n{body}",
             ranks=[0])
    return reports


# ----------------------------------------------------------------- fixtures
def roofline_table_for_config(config, model: str = "gpt2", *,
                              batch_size=None, seq_len: int = 32,
                              chip: Optional[str] = None
                              ) -> List[RooflineReport]:
    """Build a family-fixture engine from a ds_config, run ONE
    train_batch to populate the program table, and price every program
    — the ``ds_roofline report --config`` / ``ds_report roofline``
    path (mirrors ``xray_for_config``)."""
    import json as _json

    import deepspeed_tpu
    from deepspeed_tpu.analysis.doctor import _family_tiny
    from deepspeed_tpu.models.registry import resolve_family
    from deepspeed_tpu.sharding import program_table

    if isinstance(config, str):
        with open(config) as f:
            config = _json.load(f)
    config = dict(config)
    config.pop("roofline", None)  # the engine pass would double-report
    preset = _family_tiny(model)
    model_cls, make_batch, presets = resolve_family(preset)
    if preset not in presets:
        preset = sorted(presets)[0]
    mcfg = presets[preset]
    engine, _, _, _ = deepspeed_tpu.initialize(model=model_cls(mcfg),
                                               config=config)
    bs = batch_size or engine.train_batch_size()
    seq_len = min(seq_len, mcfg.n_positions)
    batch = make_batch(bs, seq_len, mcfg.vocab_size)
    engine.train_batch(batch)
    chip = chip or chip_for_engine(engine)
    reports = []
    for rec in sorted(program_table().values(), key=lambda r: r.label):
        rep = roofline_program(rec, chip=chip)
        if rep is not None:
            reports.append(rep)
    return reports


# ---------------------------------------------------------------------- CLI
def roofline_cli(argv=None) -> int:
    """``ds_roofline report`` — roofline a saved HLO dump (no jax
    needed) or a ds_config fixture (AOT, one compile per program)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="ds_roofline",
        description="Analytic roofline over compiled HLO: per-region "
                    "FLOPs/bytes, compute- vs memory-bound, predicted "
                    "step time and MFU ceiling per chip.")
    sub = p.add_subparsers(dest="cmd")
    rp = sub.add_parser("report", help="price programs against a chip")
    rp.add_argument("--hlo", action="append", default=[],
                    help="saved compiled-HLO text dump (repeatable; "
                         "needs NO jax in the process)")
    rp.add_argument("--config", help="ds_config JSON: build the fixture "
                                     "engine and price its program table")
    rp.add_argument("--model", default="gpt2",
                    help="model family/preset for --config (default gpt2)")
    rp.add_argument("--devices", type=int, default=0,
                    help="force an N-device CPU mesh for --config")
    rp.add_argument("--batch-size", type=int, default=None)
    rp.add_argument("--seq-len", type=int, default=32)
    rp.add_argument("--chip", default="cpu-sim",
                    help="chip to price against: "
                         + ", ".join(_chips.known_chips()))
    rp.add_argument("--top-k", type=int, default=8)
    rp.add_argument("--json", action="store_true", dest="as_json")
    chp = sub.add_parser("chips", help="print the per-chip peak table")
    chp.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)
    if args.cmd is None:
        p.print_help()
        return 0

    if args.cmd == "chips":
        if args.as_json:
            print(json.dumps({k: dataclasses.asdict(v)
                              for k, v in _chips.CHIPS.items()}, indent=2))
        else:
            print(f"{'chip':8} {'peak TFLOP/s':>13} {'HBM GB/s':>9} "
                  f"{'HBM GiB':>8}  note")
            for k in _chips.known_chips():
                c = _chips.CHIPS[k]
                print(f"{c.name:8} {c.peak_flops / 1e12:>13.0f} "
                      f"{c.hbm_bytes_per_s / 1e9:>9.0f} "
                      f"{c.hbm_bytes / 1024**3:>8.0f}  {c.note}")
        return 0

    try:
        _chips.resolve_chip(args.chip)
    except KeyError as e:
        print(f"ds_roofline: {e.args[0]}", file=sys.stderr)
        return 2
    reports: List[RooflineReport] = []
    for path in args.hlo:
        with open(path) as f:
            text = f.read()
        reports.append(analyze_hlo_text(text, chip=args.chip, program=path))
    if args.config:
        if args.devices:
            _force_cpu_devices(args.devices)
        reports.extend(roofline_table_for_config(
            args.config, args.model, batch_size=args.batch_size,
            seq_len=args.seq_len, chip=args.chip))
    if not reports:
        print("ds_roofline: nothing to analyze (pass --hlo and/or "
              "--config)", file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps([r.to_dict(top_k=args.top_k) for r in reports],
                         indent=2))
    else:
        print("\n\n".join(r.render(top_k=args.top_k) for r in reports))
    return 0


def _force_cpu_devices(n: int) -> None:
    """Force an n-device CPU mesh BEFORE jax backend init (the
    ``xray_cli --devices`` idiom)."""
    import os
    import re as _re

    flags = os.environ.get("XLA_FLAGS", "")
    m = _re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m:
        if int(m.group(1)) < n:
            flags = _re.sub(r"--xla_force_host_platform_device_count=\d+",
                            f"--xla_force_host_platform_device_count={n}",
                            flags)
            os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    return roofline_cli(argv)


if __name__ == "__main__":
    raise SystemExit(main())
