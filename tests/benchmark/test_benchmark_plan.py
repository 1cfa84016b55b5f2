"""The harness takes additions as data, and ``BENCHMARK.json`` keeps to
the contract's names, units and rules."""

import copy
import json
import os
import re
import shutil
import types

import pytest

from benchmark.harness import plan, shapes

BENCH = plan.load_benchmark()


def test_benchmark_json_and_its_files_are_consistent():
    assert plan.lint(BENCH) == []


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert sorted(BENCH) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
    for w in BENCH["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_every_name_keeps_to_the_allowed_characters(group):
    for entry in BENCH[group]:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}",
                            entry["name"]), entry["name"]
        if "unit" in entry:
            assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", entry["unit"])


def test_files_under_paths_are_named_from_the_allowed_characters():
    root = plan.REPO_ROOT
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.fullmatch(r"[A-Za-z0-9_.-]+", f), f


def test_the_command_names_no_file_outside_paths():
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(plan.REPO_ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_each_layer_metric_moves_a_metric_its_cells_report():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", cells):
            reported = plan.load_cell(cell, BENCH).end_to_end
            assert m["moves"] in reported, (m["name"], cell)


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def corrupt(change):
    bench = copy.deepcopy(BENCH)
    change(bench)
    return plan.lint(bench)


@pytest.mark.parametrize("change,needle", [
    (lambda b: b["workloads"][0].update(name="has space"), "bad name"),
    (lambda b: b["workloads"][0].update(name=".hidden"), "bad name"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per s"), "bad unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda b: b["per_layer"][0].update(layer="elsewhere"), "layer"),
    (lambda b: b["configs"][0].update(reduced=["hidden_size"]), "reduce"),
    (lambda b: [w.update(chips=4) for w in b["workloads"]], "4 chips"),
    (lambda b: b["end_to_end"].pop(), "setup_s"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0], name="twin")),
     "appears twice"),
])
def test_lint_sees_what_the_contract_forbids(change, needle):
    assert any(needle in line for line in corrupt(change))


def lint_with_config(tmp_path, change, reference_file=None):
    """Lint of a copy of the benchmark whose first configuration's file
    was changed (and, optionally, with one more reference file)."""
    root = tmp_path / "benchmark"
    shutil.copytree(plan.BENCH_ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "configs" / (BENCH["configs"][0]["name"] + ".json")
    config = plan.read_json(path)
    change(config)
    path.write_text(json.dumps(config))
    if reference_file:
        (root / "reference" / reference_file[0]).write_text(
            reference_file[1])
    return plan.lint(BENCH, str(root), str(tmp_path))


@pytest.mark.parametrize("change,reference_file,needle", [
    (lambda c: c.pop("reference"), None, "names no reference"),
    (lambda c: c.update(reference="no such"), None, "names no reference"),
    (lambda c: c.update(reference="nowhere"), None,
     "no file reference/nowhere.py"),
    (lambda c: c.update(reference="half"),
     ("half.py", "def check_family(cfg):\n    pass\n"),
     "lacks ['score_probes']"),
    (lambda c: c.update(expert_width=768), None,
     "'expert_width' is checked against nothing"),
    (lambda c: c.update(decoder_sparse_step=1), None,
     "'decoder_sparse_step' is checked against nothing"),
], ids=["no-key", "bad-name", "no-file", "no-interface", "unchecked-size",
        "unchecked-key"])
def test_lint_sees_a_configuration_file_nothing_vouches_for(
        tmp_path, change, reference_file, needle):
    found = lint_with_config(tmp_path, change, reference_file)
    assert any(needle in line for line in found), found


def test_lint_passes_keys_the_harness_or_the_reference_knows(tmp_path):
    assert lint_with_config(tmp_path, lambda c: c.update(
        num_experts=0, attention_dropout=0.0)) == []
    assert lint_with_config(
        tmp_path / "again", lambda c: c.update(reference="wider",
                                               block_length=4),
        ("wider.py", NEW_FAMILY)) == []


@pytest.mark.parametrize("model,stated,needle", [
    ("tiny-moe", {"num_experts": 128}, "num_experts: file 128, runs 4"),
    ("tiny-moe", {"n_routed_experts": 8}, "n_routed_experts: file 8"),
    ("tiny-moe", {"num_experts_per_tok": 8}, "num_experts_per_tok"),
    ("tiny-moe", {"moe_intermediate_size": 768}, "moe_intermediate_size"),
    ("tiny-moe", {"norm_topk_prob": False}, "norm_topk_prob"),
    ("tiny-deepseek", {"n_shared_experts": 4}, "n_shared_experts"),
    ("tiny-deepseek", {"first_k_dense_replace": 3},
     "first_k_dense_replace"),
    ("tiny-deepseek", {"kv_lora_rank": 512}, "kv_lora_rank"),
    ("tiny-deepseek", {"q_lora_rank": 1536}, "q_lora_rank"),
    ("tiny-deepseek", {"qk_rope_head_dim": 64}, "qk_rope_head_dim"),
    ("tiny-deepseek", {"v_head_dim": 128}, "v_head_dim"),
    ("tiny-qwen3", {"attention_bias": True}, "attention_bias"),
])
def test_a_stated_size_that_differs_from_what_runs_is_seen(model, stated,
                                                           needle):
    from tpuserve.models.config import get_model_config
    cfg = get_model_config(model)
    wrong = plan.architecture_mismatches(stated, cfg)
    assert len(wrong) == 1 and needle in wrong[0], wrong
    runs = {key: getattr(cfg, plan.FIXED[key]) for key in stated}
    assert plan.architecture_mismatches(runs, cfg) == []


def test_a_width_can_never_be_reduced():
    with pytest.raises(ValueError):
        plan.architecture_overrides({"reduced": ["hidden_size"],
                                     "hidden_size": 8})
    assert plan.architecture_overrides(
        {"reduced": ["num_hidden_layers"], "num_hidden_layers": 16}) == \
        {"num_layers": 16}


NEW_FAMILY = '''"""A reference of another family, as a later PR would add
it: its own scoring, the sizes of its family that are checked, the keys
that size nothing."""
FIXED = {"block_length": "block_length"}
DESCRIPTIVE = ("denoising_steps", "mask_token_id")


def check_family(cfg):
    if not getattr(cfg, "block_length", 0):
        raise ValueError("not a block-diffusion model")


def score_probes(params, cfg, probes):
    return [[0.0] for _, toks, _ in probes for _ in toks]
'''


def test_additions_are_data(tmp_path):
    """A configuration of another family with its own reference, a traffic
    mix, a cell and a per-layer metric added as files (and entries) are
    found with no edit to the harness."""
    root = tmp_path / "benchmark"
    shutil.copytree(plan.BENCH_ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(folder, p), "rb").read()
              for folder in [root / "harness"] for p in os.listdir(folder)
              if p.endswith(".py")}
    config = plan.read_json(root / "configs" / (BENCH["configs"][0]["name"]
                                                + ".json"))
    config.update(reduced=["num_hidden_layers"], num_hidden_layers=2,
                  source="https://example.org/new", reference="new_family",
                  block_length=4, denoising_steps=4, mask_token_id=7)
    (root / "configs" / "new-model.json").write_text(json.dumps(config))
    (root / "reference" / "new_family.py").write_text(NEW_FAMILY)
    mix = plan.read_json(root / "traffic" / (BENCH["workloads"][0]["traffic"]
                                             + ".json"))
    mix.update(pool=8, end_to_end=["out_tok_s"])
    (root / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (root / "cells" / "new-model.new-mix.json").write_text(json.dumps(
        {"config": "new-model", "traffic": "new-mix", "clients": 5}))
    (root / "layer_metrics" / "new.metric.py").write_text(
        'LAYER = "scheduler"\nUNIT = "count"\nBETTER = "lower"\n'
        'MOVES = "out_tok_s"\nSOURCE = "program_span"\n\n\n'
        'def compute(run):\n    return len(run["steps"])\n')
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": "new-model", "source": "https://example.org/new",
        "file": "benchmark/configs/new-model.json",
        "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_s" and "workloads" in m:
            m["workloads"].append("new-model.new-mix")
    bench["per_layer"].append({
        "name": "new.metric", "unit": "count", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "out_tok_s", "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert plan.lint(bench, str(root), str(tmp_path)) == []
    cell = plan.load_cell("new-model.new-mix", bench, str(root))
    assert cell.params["clients"] == 5 and cell.traffic["pool"] == 8
    assert cell.config["num_hidden_layers"] == 2
    assert cell.reference.__file__ == str(root / "reference"
                                          / "new_family.py")
    rows = cell.reference.score_probes(None, None, [([1, 2], [3, 4, 5], {})])
    assert len(rows) == 3
    assert plan.architecture_mismatches(
        {"block_length": cell.config["block_length"]},
        types.SimpleNamespace(block_length=8),
        cell.reference) == ["block_length: file 4, runs 8"]
    old = plan.load_cell(BENCH["workloads"][0]["name"], bench, str(root))
    assert old.reference.__file__ != cell.reference.__file__
    assert plan.unchecked_keys(cell.config, old.reference) == [
        "block_length", "denoising_steps", "mask_token_id"]
    assert "new.metric" in cell.per_layer
    assert "new.metric" not in plan.load_cell(
        BENCH["workloads"][0]["name"], bench, str(root)).per_layer
    readers = plan.discover_layer_metrics(str(root))
    assert readers["new.metric"].compute({"steps": [1, 2, 3]}) == 3
    after = {p: open(os.path.join(root / "harness", p), "rb").read()
             for p in before}
    assert after == before


def test_harness_and_command_name_no_cell_config_mix_or_metric():
    names = {e["name"] for g in ("configs", "workloads", "per_layer")
             for e in BENCH[g]} | {w["traffic"] for w in BENCH["workloads"]}
    names |= {f[:-3] for f in os.listdir(os.path.join(plan.BENCH_ROOT,
                                                      "reference"))
              if f.endswith(".py") and not f.startswith("_")}
    names |= {plan.read_json(os.path.join(plan.REPO_ROOT, c["file"]))
              ["reference"] for c in BENCH["configs"]}
    assert "dense_gqa" in names
    sources = [os.path.join(plan.BENCH_ROOT, "run.py")] + [
        os.path.join(plan.BENCH_ROOT, "harness", f)
        for f in os.listdir(os.path.join(plan.BENCH_ROOT, "harness"))
        if f.endswith(".py")]
    for path in sources:
        text = open(path).read()
        for name in names:
            assert name not in text, (path, name)


class FakeScheduler:
    """The scheduler's bucket rules at the server's defaults."""

    def __init__(self):
        from tpuserve.runtime.scheduler import Scheduler, SchedulerConfig
        self.cfg = SchedulerConfig()
        self._s = Scheduler.__new__(Scheduler)
        self._s.cfg = self.cfg

    def __getattr__(self, name):
        return getattr(self._s, name)


def test_warm_shapes_cover_the_traffic_and_nothing_else():
    bounds = {"prompt_min": 32, "prompt_max": 3072, "output_max": 512,
              "total_max": 3584}
    got = shapes.warm_shapes(FakeScheduler(), bounds)
    lengths = sorted({l for _, l in got["prefill_buckets"]})
    assert lengths == [32, 64, 128, 256, 512, 1024, 2048]
    assert (8, 1024) in got["prefill_buckets"]
    assert (4, 2048) in got["prefill_buckets"]
    assert (8, 2048) not in got["prefill_buckets"]      # 16384 > 8192 budget
    assert len(got["prefill_buckets"]) == 27
    assert got["chunk_buckets"] == [32, 64, 128, 256, 512, 1024, 2048]
    assert got["decode_buckets"] == [4, 8, 16, 32, 64]
    short = shapes.warm_shapes(FakeScheduler(), {
        "prompt_min": 100, "prompt_max": 500, "output_max": 100,
        "total_max": 600})
    assert short["chunk_buckets"] == []
    assert sorted({l for _, l in short["prefill_buckets"]}) == \
        [128, 256, 512, 1024]
