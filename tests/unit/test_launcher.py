"""Launcher + elasticity unit tests.

Mirrors the reference's pure-unit launcher suite (tests/unit/launcher/
test_run.py: hostfile parsing, include/exclude resolution) and elasticity
math checks — no processes are spawned.
"""

import os

import pytest

from deepspeed_tpu.elasticity import (ElasticityConfig, ElasticityError,
                                      compute_elastic_config,
                                      get_candidate_batch_sizes,
                                      get_compatible_chip_counts)
from deepspeed_tpu.launcher.launch import build_env, decode_world_info
from deepspeed_tpu.launcher.runner import (encode_world_info, fetch_hostfile,
                                           parse_inclusion_exclusion)


@pytest.fixture
def hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text(
        "# comment line\n"
        "worker-0 slots=4\n"
        "worker-1 slots=4   # trailing comment\n"
        "\n"
        "worker-2 slots=8\n")
    return str(p)


class TestHostfile:
    def test_parse(self, hostfile):
        pool = fetch_hostfile(hostfile)
        assert pool == {"worker-0": 4, "worker-1": 4, "worker-2": 8}
        assert list(pool) == ["worker-0", "worker-1", "worker-2"]

    def test_missing_file(self):
        assert fetch_hostfile("/nonexistent/hostfile") == {}

    def test_malformed(self, tmp_path):
        p = tmp_path / "bad"
        p.write_text("worker-0 slots=abc\n")
        with pytest.raises(ValueError, match="malformed"):
            fetch_hostfile(str(p))

    def test_duplicate(self, tmp_path):
        p = tmp_path / "dup"
        p.write_text("w slots=2\nw slots=4\n")
        with pytest.raises(ValueError, match="duplicate"):
            fetch_hostfile(str(p))


class TestIncludeExclude:
    POOL = {"worker-0": 4, "worker-1": 4, "worker-2": 8}

    def test_no_filter(self):
        active = parse_inclusion_exclusion(self.POOL, "", "")
        assert active == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 1, 2, 3],
                          "worker-2": list(range(8))}

    def test_include_hosts(self):
        active = parse_inclusion_exclusion(self.POOL, "worker-0@worker-2", "")
        assert list(active) == ["worker-0", "worker-2"]

    def test_include_slots(self):
        active = parse_inclusion_exclusion(self.POOL, "worker-1:0,2", "")
        assert active == {"worker-1": [0, 2]}

    def test_include_slot_range(self):
        active = parse_inclusion_exclusion(self.POOL, "worker-2:0-3", "")
        assert active == {"worker-2": [0, 1, 2, 3]}

    def test_exclude_host(self):
        active = parse_inclusion_exclusion(self.POOL, "", "worker-1")
        assert list(active) == ["worker-0", "worker-2"]

    def test_exclude_slots(self):
        active = parse_inclusion_exclusion(self.POOL, "", "worker-0:1,3")
        assert active["worker-0"] == [0, 2]

    def test_exclude_all_slots_drops_host(self):
        active = parse_inclusion_exclusion(self.POOL, "", "worker-0:0-3")
        assert "worker-0" not in active

    def test_both_filters_error(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            parse_inclusion_exclusion(self.POOL, "worker-0", "worker-1")

    def test_unknown_host(self):
        with pytest.raises(ValueError, match="not in hostfile"):
            parse_inclusion_exclusion(self.POOL, "nope", "")


class TestLaunchEnv:
    def test_world_info_roundtrip(self):
        active = {"a": [0, 1], "b": [0, 1, 2, 3]}
        assert decode_world_info(encode_world_info(active)) == active

    def test_build_env(self):
        active = {"hostA": [0, 1, 2, 3], "hostB": [0, 1, 2, 3]}
        env = build_env(active, node_rank=1, master_addr="hostA", master_port=9999,
                        base_env={})
        assert env["JAX_COORDINATOR_ADDRESS"] == "hostA:9999"
        assert env["JAX_NUM_PROCESSES"] == "2"
        assert env["JAX_PROCESS_ID"] == "1"
        assert env["RANK"] == "1" and env["WORLD_SIZE"] == "2"


class TestElasticity:
    def test_candidates_bounded(self):
        cands = get_candidate_batch_sizes([2, 4], 64)
        assert all(b <= 64 for b in cands)
        assert 64 in cands and 2 in cands

    def test_compatible_counts(self):
        # batch 64, micro candidates [2,4]: every divisor world ≤ 16 works
        valid = get_compatible_chip_counts(64, [2, 4], 1, 16)
        assert valid == [1, 2, 4, 8, 16]

    def test_compatible_multiple_of(self):
        valid = get_compatible_chip_counts(64, [2, 4], 1, 16, multiple_of=4)
        assert valid == [4, 8, 16]

    def test_compute_config(self):
        ds = {"elasticity": {"enabled": True, "max_train_batch_size": 512,
                             "micro_batch_sizes": [2, 4, 8], "min_gpus": 1,
                             "max_gpus": 64, "version": 0.1}}
        batch, valid = compute_elastic_config(ds)
        assert batch <= 512 and len(valid) >= 7
        for w in valid:
            per = batch // w
            assert any(per % mb == 0 for mb in [2, 4, 8])

    def test_compute_config_with_world(self):
        ds = {"elasticity": {"enabled": True, "max_train_batch_size": 512,
                             "micro_batch_sizes": [2, 4, 8], "min_gpus": 1,
                             "max_gpus": 64, "version": 0.1}}
        batch, valid, micro = compute_elastic_config(ds, world_size=valid_w(ds))
        assert micro in [2, 4, 8]

    def test_batch_keys_clash(self):
        ds = {"train_batch_size": 32,
              "elasticity": {"enabled": True, "max_train_batch_size": 512,
                             "micro_batch_sizes": [2], "min_gpus": 1, "max_gpus": 8}}
        with pytest.raises(ElasticityError, match="conflict"):
            compute_elastic_config(ds)

    def test_disabled(self):
        with pytest.raises(ElasticityError):
            compute_elastic_config({"elasticity": {"enabled": False}})

    def test_bad_range(self):
        with pytest.raises((ElasticityError, ValueError)):
            ElasticityConfig(enabled=True, min_gpus=8, max_gpus=2)

    def test_v02_whole_hosts(self):
        ds = {"elasticity": {"enabled": True, "max_train_batch_size": 1024,
                             "micro_batch_sizes": [4, 8], "min_gpus": 4,
                             "max_gpus": 256, "version": 0.2,
                             "num_gpus_per_node": 4, "model_parallel_size": 2}}
        batch, valid = compute_elastic_config(ds)
        assert all(w % 8 == 0 for w in valid)


def valid_w(ds):
    from deepspeed_tpu.elasticity import compute_elastic_config as cec

    _, valid = cec(ds)
    return valid[-1]


class TestEnvReport:
    def test_runs(self, capsys):
        from deepspeed_tpu.env_report import main

        assert main() == 0
        out = capsys.readouterr().out
        assert "deepspeed_tpu environment report" in out
        assert "jax" in out
