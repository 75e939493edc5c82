"""A configuration, a traffic mix and a per-layer metric are found by the
name BENCHMARK.json gives them: adding them adds files and edits none."""
import json
import shutil

from bench import run as bench_run
from bench import spec


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    for kind in ("configs", "traffic", "layers", "flops"):
        (root / kind).mkdir(parents=True)
    cfg = json.loads(open(f"{spec.BENCH_DIR}/configs/internvl2-1b.json")
                     .read())
    cfg["name"] = "throwaway"
    (root / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    mix = json.loads(open(f"{spec.BENCH_DIR}/traffic/spot.json").read())
    mix["bids"] = [0.7] * 4
    (root / "traffic" / "lowbid.json").write_text(json.dumps(mix))
    (root / "layers" / "twice_window.py").write_text(
        "def read(record):\n    return 2 * record['window_s']\n")
    shutil.copy(f"{spec.BENCH_DIR}/flops/vlm.py", root / "flops" / "vlm.py")
    benchmark = {
        "workloads": [{"name": "throwaway.lowbid", "config": "throwaway",
                       "traffic": "lowbid", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
        "per_layer": [{"name": "twice_window", "unit": "s",
                       "workloads": ["throwaway.lowbid"]},
                      {"name": "elsewhere", "unit": "s",
                       "workloads": ["other.cell"]}]}
    cell = spec.load_cell("throwaway.lowbid", str(root), benchmark)
    assert cell.config["name"] == "throwaway"
    assert cell.traffic["bids"] == [0.7] * 4
    assert [m["name"] for m in cell.per_layer] == ["twice_window"]
    assert cell.limits is None
    assert bench_run.layer_value("twice_window", {"window_s": 1.5},
                                 str(root)) == 3.0
    assert bench_run.flops_per_row(cell.config, str(root)) == \
        bench_run.flops_per_row(cell.config)


def test_every_cell_names_files_that_exist():
    benchmark = json.loads(open(f"{spec.ROOT}/BENCHMARK.json").read())
    for w in benchmark["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        spec.load_module("flops", cell.config["family"])
        spec.load_module("reference", cell.config["reference"])
        for m in cell.per_layer:
            spec.load_module("layers", m["name"])
