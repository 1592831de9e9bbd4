"""Autotuner tests — reference tests/unit/autotuning role: candidate space,
tuner ordering, real measured experiments, OOM/error pruning, result files."""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.autotuning import Autotuner, AutotuningConfig
from deepspeed_tpu.models.simple import SimpleModel

HIDDEN = 16


def _model_factory(remat=None):
    return SimpleModel(hidden_dim=HIDDEN, nlayers=2)


def _batch_factory(batch_size):
    rng = np.random.RandomState(0)
    return (rng.randn(batch_size, HIDDEN).astype(np.float32),
            rng.randn(batch_size, HIDDEN).astype(np.float32))


BASE = {"optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0}


def _tuning(tmp_path, **kw):
    return AutotuningConfig(enabled=True, start_profile_step=1, end_profile_step=2,
                            results_dir=str(tmp_path / "results"),
                            exps_dir=str(tmp_path / "exps"),
                            mbs_list=[1, 2], zero_stage_list=[0, 1],
                            remat_list=["none"], **kw)


class TestAutotuner:
    def test_candidate_space(self, tmp_path):
        at = Autotuner(_model_factory, _batch_factory, BASE, _tuning(tmp_path))
        cands = at.candidate_space()
        assert len(cands) == 4  # 2 mbs x 2 stages x 1 remat
        assert all("_tune" in c for c in cands)

    def test_tune_finds_best_and_writes_results(self, tmp_path):
        at = Autotuner(_model_factory, _batch_factory, BASE, _tuning(tmp_path))
        best = at.tune()
        assert best is not None
        assert "_tuned" in best
        ok = [e for e in at.experiments if e.status == "ok"]
        assert len(ok) >= 1
        # best really is the max-metric experiment
        assert max(e.metric_val for e in ok) == \
            max(e.metric_val for e in at.experiments)
        summary = json.load(open(os.path.join(at.tuning.results_dir, "summary.json")))
        assert summary["num_experiments"] == len(at.experiments)
        assert os.path.isfile(os.path.join(at.tuning.results_dir,
                                           "ds_config_optimal.json"))

    def test_bad_candidate_is_pruned_not_fatal(self, tmp_path):
        # train_batch_size 3*8 with mbs 3: fine; mbs 5 against dp=8 divides
        # train_batch 40... make an invalid one via a bogus optimizer instead
        bad_base = {"optimizer": {"type": "NoSuchOpt", "params": {}},
                    "steps_per_print": 0}
        at = Autotuner(_model_factory, _batch_factory, bad_base,
                       _tuning(tmp_path, tuner_early_stopping=0))
        best = at.tune()
        assert best is None
        assert all(e.status in ("error", "oom") for e in at.experiments)

    def test_model_based_ordering_prefers_big_batches(self, tmp_path):
        at = Autotuner(_model_factory, _batch_factory, BASE, _tuning(tmp_path))
        ordered = at._order(at.candidate_space())
        mbs = [c["_tune"]["micro_batch"] for c in ordered]
        assert mbs[0] == max(mbs)

    def test_latency_metric(self, tmp_path):
        at = Autotuner(_model_factory, _batch_factory, BASE,
                       _tuning(tmp_path, metric="latency"))
        best = at.tune()
        assert best is not None
        ok = [e for e in at.experiments if e.status == "ok"]
        assert all(e.metric_val <= 0 for e in ok)   # latency metric = -step_time


class TestAutotunerAxes:
    def test_gas_tp_offload_flash_axes(self, tmp_path):
        """The widened space (reference tuner sweeps ZeRO sub-knobs too):
        gas/tp/offload/flash-block multiply the candidate set and land in the
        generated ds_configs."""
        t = _tuning(tmp_path, gas_list=[1, 2], tp_list=[1, 2],
                    offload_list=[False, True], flash_block_list=[None, 256])
        at = Autotuner(_model_factory, _batch_factory, BASE, t)
        cands = at.candidate_space()
        # 2 mbs x 2 stages x 1 remat x 2 gas x 2 tp x 2 offload x 2 fb
        assert len(cands) == 64
        got = {(c["_tune"]["gas"], c["_tune"]["tp"], c["_tune"]["offload"],
                c["_tune"]["flash_block"]) for c in cands}
        assert (2, 2, True, 256) in got
        gas2 = next(c for c in cands if c["_tune"]["gas"] == 2
                    and c["_tune"]["tp"] == 2)
        assert gas2["gradient_accumulation_steps"] == 2
        assert gas2["tpu"]["tensor"] == 2
        # tp not dividing the device count is dropped
        t2 = _tuning(tmp_path, tp_list=[1, 3])
        at2 = Autotuner(_model_factory, _batch_factory, BASE, t2)
        assert all(c["_tune"]["tp"] == 1 for c in at2.candidate_space())

    def test_hbm_cost_model_prunes_hopeless(self, tmp_path, monkeypatch):
        """A candidate whose first-order HBM estimate exceeds the budget is
        recorded as 'pruned' without compiling."""
        import dataclasses

        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, synthetic_lm_batch

        cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                         n_head=4, use_flash_attention=False)

        def model_factory(remat="attn", flash_block=None):
            return GPT2Model(dataclasses.replace(
                cfg, remat=remat if remat != "none" else False))

        def batch_factory(bs):
            return synthetic_lm_batch(bs, 32, cfg.vocab_size)

        t = AutotuningConfig(enabled=True, start_profile_step=1,
                             end_profile_step=2,
                             results_dir=str(tmp_path / "results"),
                             exps_dir=str(tmp_path / "exps"),
                             mbs_list=[1], zero_stage_list=[0],
                             remat_list=["none"])
        at = Autotuner(model_factory, batch_factory, BASE, t, seq_len=32)
        est = at.estimate_hbm_bytes({"micro_batch": 1, "zero": 0,
                                     "remat": "none", "gas": 1, "tp": 1},
                                    n_dev=1)
        assert est is not None and est > 0
        # pretend the chip is tiny: everything prunes, nothing compiles
        class FakeDev:
            def memory_stats(self):
                return {"bytes_limit": 1024}
        import jax
        monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
        ran = {"n": 0}
        monkeypatch.setattr(at, "_run_one",
                            lambda exp: ran.__setitem__("n", ran["n"] + 1))
        at.tune()
        assert ran["n"] == 0
        assert all(e.status == "pruned" for e in at.experiments)

    def test_model_based_order_prefers_inhbm_over_offload(self, tmp_path):
        t = _tuning(tmp_path, offload_list=[True, False])
        at = Autotuner(_model_factory, _batch_factory, BASE, t)
        ordered = at._order(at.candidate_space())
        first_off = next(i for i, c in enumerate(ordered)
                         if c["_tune"]["offload"])
        assert all(not c["_tune"]["offload"] for c in ordered[:first_off])


class TestDsTuneCLI:
    def test_family_dispatch_bert(self, tmp_path, capsys, monkeypatch):
        """ds_tune drives non-GPT2 families (reference autotuning runner
        role): bert preset + MLM batches through a real 2-candidate tune."""
        import runpy
        import sys

        monkeypatch.setattr(sys, "argv", [
            "ds_tune", "--model", "bert-tiny", "--seq", "64",
            "--mbs", "2", "--remat", "none", "--steps", "1",
            "--output", str(tmp_path)])
        runpy.run_path(os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "bin", "ds_tune"),
            run_name="__main__")
        out = capsys.readouterr().out.strip().splitlines()[-1]
        res = json.loads(out)
        assert res["status"] == "ok"
        assert res["tuned"]["micro_batch"] == 2

    def test_report_describes_the_preset_it_was_given(self, tmp_path, capsys,
                                                      monkeypatch):
        """The tuner tunes the system, never the model: every candidate is
        built at the preset's own published n_head and the report carries
        no head_relayout marker."""
        import runpy
        import sys

        from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Model

        built = []
        init = GPT2Model.__init__

        def spy(self, config, *a, **kw):
            built.append(config.n_head)
            init(self, config, *a, **kw)

        monkeypatch.setattr(GPT2Model, "__init__", spy)
        monkeypatch.setattr(sys, "argv", [
            "ds_tune", "--model", "gpt2-tiny", "--seq", "64",
            "--mbs", "2", "--remat", "none", "--steps", "1",
            "--output", str(tmp_path)])
        runpy.run_path(os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "bin", "ds_tune"),
            run_name="__main__")
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["status"] == "ok" and "head_relayout" not in res
        assert "n_head" not in res["tuned"]
        assert built and set(built) == {PRESETS["gpt2-tiny"].n_head}
