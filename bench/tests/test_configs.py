"""Each configuration file states its source's keys once, and the ``model``
block it runs agrees with them, key by key, except where ``reduced`` says
it departs; and the harness turns every group of a ``model`` block into
the program's configuration."""
import dataclasses
import glob
import json
import os

import pytest

import jax

from bench import spec, train_cell
from bench.tests.tiny import shrink

FILES = sorted(glob.glob(os.path.join(spec.BENCH_DIR, "configs", "*.json")))


def _at(tree: dict, path: str):
    for part in path.split("."):
        tree = tree[part]
    return tree


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_model_block_runs_the_source_values(path):
    config = json.load(open(path))
    reduced = set(config["reduced"])
    for key, model_key in config["model_keys"].items():
        if model_key is None:
            assert key in reduced, f"{key} is not run but not in reduced"
        elif key not in reduced:
            assert _at(config["model"], model_key) == config[key], key
    for key in reduced:
        assert key in config["assumed"], f"{key} is cut without a reason"


def test_reduced_keys_match_benchmark_json():
    benchmark = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    for entry in benchmark["configs"]:
        config = json.load(open(os.path.join(spec.ROOT, entry["file"])))
        assert sorted(entry["reduced"]) == sorted(config["reduced"])
        assert entry["source"] == config["source"]


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_model_config_runs_every_key_of_the_file(path):
    config = json.load(open(path))
    cfg = train_cell.model_config(config)
    for key, value in config["model"].items():
        if isinstance(value, dict):
            for field, v in value.items():
                assert getattr(getattr(cfg, key), field) == v, (key, field)
        else:
            assert getattr(cfg, key) == value, key


def _deepseek(**model):
    """A configuration file of the DeepSeek-V2-Lite architecture, whose
    ``model`` block holds ``model``."""
    config = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                         "internvl2-1b.json")))
    config["arch"] = "deepseek-v2-lite-16b"
    config["model"] = dict(num_layers=6, dtype="bfloat16",
                           param_dtype="bfloat16", **model)
    return config


def test_moe_and_mla_groups_merge_into_the_arch():
    from repro.configs import ARCHS

    base = ARCHS["deepseek-v2-lite-16b"]
    cfg = train_cell.model_config(_deepseek(
        moe={"num_experts": 8, "aux_loss_weight": 0.001},
        mla={"kv_lora_rank": 256}))
    assert cfg.num_layers == 6
    assert cfg.moe == dataclasses.replace(base.moe, num_experts=8,
                                          aux_loss_weight=0.001)
    assert cfg.mla == dataclasses.replace(base.mla, kv_lora_rank=256)
    assert cfg.moe.top_k == 6 and cfg.mla.v_head_dim == 128


@pytest.mark.parametrize("group", ["ssm", "vision", "encoder"])
def test_a_group_the_arch_lacks_is_refused(group):
    with pytest.raises(ValueError, match=f"model.{group}"):
        train_cell.model_config(_deepseek(**{group: {}}))


def test_a_dict_that_is_no_group_is_refused():
    with pytest.raises(ValueError, match="model.rope_theta"):
        train_cell.model_config(_deepseek(rope_theta={"base": 1e4}))
    with pytest.raises(TypeError, match="num_experts_per_tok"):
        train_cell.model_config(_deepseek(moe={"num_experts_per_tok": 6}))


def test_shrink_cuts_the_moe_and_mla_groups():
    config = shrink(_deepseek(moe={"num_experts": 64, "top_k": 6},
                              mla={"kv_lora_rank": 512}))
    assert "vision" not in config["model"]
    job = train_cell.job_config(config)
    assert (job.model.moe.num_experts, job.model.moe.top_k) == (4, 2)
    assert job.model.mla.kv_lora_rank == 16
    from repro.train import zoo_program

    params = jax.eval_shape(lambda: zoo_program.init_zoo_state(
        job.model, job, jax.random.PRNGKey(0)))["params"]
    assert params["layers"]["moe"]["router"].shape == (2, 64, 4)
    assert params["layers"]["mla"]["w_dkv"].shape[-1] == 16 + 8
