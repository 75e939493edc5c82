"""Each configuration file states its source's keys once, and the ``model``
block it runs agrees with them, key by key, except where ``reduced`` says
it departs."""
import glob
import json
import os

import pytest

from bench import spec

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
