"""Finding a cell's files by name.  Standard library only (a reference
file imports what it needs).

Everything that belongs to one configuration, one traffic mix, one cell,
one per-layer metric or one model family's plain reference is a file of
its own under the benchmark's root:

    configs/<configuration>.json      traffic/<mix>.json
    cells/<cell>.json                 layer_metrics/<metric>.py
    reference/<family>.py

``BENCHMARK.json`` names the first four and a configuration file names its
reference (``"reference": "<family>"``); this module finds them.  Nothing
here (or in ``run.py``) names a configuration, a mix, a cell, a metric or a
reference, so a later PR adds a cell, of a new architecture too, by adding
files and entries and edits nothing.

A reference file has ``check_family(model_cfg)``, which raises ValueError
on an architecture it does not describe, and ``score_probes(params,
model_cfg, probes)``: for ``probes``, a list of ``(prompt ids, served
token ids, the choice's logprobs object as the server returned it)``, one
float32 row of log-probabilities over the vocabulary for every served
token, in served order.  It may also state which further keys of its
family's ``config.json`` are checked against which ModelConfig field
(``FIXED``, as below) and which size nothing (``DESCRIPTIVE``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# HF config.json key -> ModelConfig field, for the keys a configuration
# file states.  ``reduced`` may name only the CUTTABLE group (a depth or a
# context cut); the FIXED group (every width, and what changes the
# mathematics) can only be checked against what runs.
CUTTABLE = {"num_hidden_layers": "num_layers",
            "max_position_embeddings": "max_position_embeddings"}
FIXED = {"hidden_size": "hidden_size",
         "intermediate_size": "intermediate_size",
         "num_attention_heads": "num_heads",
         "num_key_value_heads": "num_kv_heads",
         "head_dim": "head_dim",
         "vocab_size": "vocab_size",
         "sliding_window": "sliding_window",
         "tie_word_embeddings": "tie_word_embeddings",
         "rope_theta": "rope_theta",
         "rms_norm_eps": "norm_eps",
         "attention_bias": "attention_bias",
         "num_experts": "num_experts",
         "n_routed_experts": "num_experts",
         "num_experts_per_tok": "num_experts_per_tok",
         "moe_intermediate_size": "expert_intermediate_size",
         "norm_topk_prob": "norm_topk_prob",
         "n_shared_experts": "moe_shared_experts",
         "first_k_dense_replace": "moe_first_k_dense",
         "kv_lora_rank": "mla_kv_lora_rank",
         "q_lora_rank": "mla_q_lora_rank",
         "qk_nope_head_dim": "mla_qk_nope_head_dim",
         "qk_rope_head_dim": "mla_qk_rope_head_dim",
         "v_head_dim": "mla_v_head_dim"}
# A configuration file's own keys, and the keys of a ``config.json`` that
# describe and size nothing.  Any other key has to be in CUTTABLE, FIXED or
# the reference's own lists: a size that nothing checks is refused.
OWN_KEYS = ("model", "source", "chips", "deployment", "reduced", "assumed",
            "server_args", "expect", "reference", "status")
DESCRIPTIVE = ("model_type", "architectures", "hidden_act", "torch_dtype",
               "use_sliding_window", "transformers_version", "use_cache",
               "initializer_range", "attention_dropout", "bos_token_id",
               "eos_token_id", "pad_token_id")
REFERENCE_INTERFACE = ("check_family", "score_probes")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    traffic_path: str
    params: dict                 # the cell file: rate or clients
    end_to_end: tuple            # metric names, setup_s included
    per_layer: tuple             # metric names
    units: dict = dataclasses.field(default_factory=dict)   # by metric name
    reference: object = None     # the module the configuration names


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo_root: str = REPO_ROOT) -> dict:
    return read_json(os.path.join(repo_root, "BENCHMARK.json"))


def _metrics_for(entries: list, cell: str) -> tuple:
    return tuple(m["name"] for m in entries
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, bench: dict, bench_root: str = BENCH_ROOT) -> Cell:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       f"{[w['name'] for w in bench['workloads']]}")
    params = read_json(os.path.join(bench_root, "cells", name + ".json"))
    for key in ("config", "traffic"):
        if params[key] != entry[key]:
            raise ValueError(f"cell {name}: {key} {params[key]!r} in the "
                             f"cell file, {entry[key]!r} in BENCHMARK.json")
    traffic_path = os.path.join(bench_root, "traffic",
                                entry["traffic"] + ".json")
    config = read_json(os.path.join(bench_root, "configs",
                                    entry["config"] + ".json"))
    if config["chips"] != entry["chips"]:
        raise ValueError(f"cell {name}: configuration wants "
                         f"{config['chips']} chip(s), the cell "
                         f"{entry['chips']}")
    return Cell(name=name, chips=entry["chips"],
                reference=load_reference(config, bench_root),
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"],
                traffic=read_json(traffic_path), traffic_path=traffic_path,
                params=params,
                end_to_end=_metrics_for(bench["end_to_end"], name),
                per_layer=_metrics_for(bench["per_layer"], name),
                units={m["name"]: m["unit"] for m in
                       bench["end_to_end"] + bench["per_layer"]})


def _load_module(kind: str, name: str, path: str):
    spec = importlib.util.spec_from_file_location(
        kind + "_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def discover_layer_metrics(bench_root: str = BENCH_ROOT) -> dict:
    """``{metric name: module}`` for every file in ``layer_metrics/``.
    A reader has LAYER, UNIT, BETTER, MOVES, SOURCE and ``compute(run)``,
    which returns a number, or None where it finds nothing to read."""
    found = {}
    folder = os.path.join(bench_root, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        found[fname[:-3]] = _load_module("layer_metric", fname[:-3],
                                         os.path.join(folder, fname))
    return found


def load_reference(config: dict, bench_root: str = BENCH_ROOT):
    """The plain reference a configuration file names, loaded by path from
    ``reference/<name>.py``.  ValueError, with the sentence, where the file
    names none, the name has no file or the file lacks the interface."""
    name = config.get("reference")
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"the configuration names no reference "
                         f"(\"reference\": {name!r})")
    path = os.path.join(bench_root, "reference", name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"reference {name!r}: no file reference/{name}.py")
    mod = _load_module("reference", name, path)
    lacks = [f for f in REFERENCE_INTERFACE
             if not callable(getattr(mod, f, None))]
    if lacks:
        raise ValueError(f"reference {name!r} lacks {lacks}")
    return mod


def checked_keys(reference=None) -> dict:
    """HF key -> ModelConfig field for every key that is checked against
    what runs: this module's, then the reference's own."""
    return {**getattr(reference, "FIXED", {}), **CUTTABLE, **FIXED}


def architecture_overrides(config: dict) -> dict:
    """ModelConfig fields to replace on the registered model: the keys the
    configuration file lists under ``reduced``, and no width."""
    out = {}
    for key in config.get("reduced", ()):
        if key not in CUTTABLE:
            raise ValueError(f"configuration may not reduce {key!r}: only "
                             f"{sorted(CUTTABLE)} can be cut")
        out[CUTTABLE[key]] = config[key]
    return out


def architecture_mismatches(config: dict, model_cfg, reference=None) -> list:
    """Where the architecture the file states differs from the ModelConfig
    that runs.  Empty means the file holds the configuration as it runs."""
    bad = []
    for key, field in checked_keys(reference).items():
        if key in config and getattr(model_cfg, field) != config[key]:
            bad.append(f"{key}: file {config[key]!r}, runs "
                       f"{getattr(model_cfg, field)!r}")
    return bad


def unchecked_keys(config: dict, reference=None) -> list:
    """Keys of a configuration file that nothing checks and no list calls
    descriptive."""
    known = set(checked_keys(reference)) | set(OWN_KEYS) | set(DESCRIPTIVE) \
        | set(getattr(reference, "DESCRIPTIVE", ()))
    return sorted(k for k in config if k not in known)


def lint(bench: dict, bench_root: str = BENCH_ROOT,
         repo_root: str = REPO_ROOT) -> list:
    """What is wrong with ``BENCHMARK.json`` and the files it names, as a
    list of sentences.  Empty means nothing found."""
    bad = []

    def name_ok(what, s):
        if not isinstance(s, str) or not NAME.match(s):
            bad.append(f"{what}: bad name {s!r}")

    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        for n in names:
            name_ok(group, n)
        if len(set(names)) != len(names):
            bad.append(f"{group}: a name appears twice")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better is {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source is {m['source']!r}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric's source")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic appears twice")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for 4 chips")
    for c in bench["configs"]:
        path = os.path.join(repo_root, c["file"])
        if not os.path.isfile(path):
            bad.append(f"config {c['name']}: no file {c['file']}")
            continue
        data = read_json(path)
        if sorted(data.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: reduced differs from its file")
        if data.get("source") != c["source"]:
            bad.append(f"config {c['name']}: source differs from its file")
        for key in c["reduced"]:
            name_ok(f"config {c['name']} reduced", key)
            if key not in CUTTABLE:
                bad.append(f"config {c['name']}: reduces {key!r}")
        if not any(w["config"] == c["name"] for w in bench["workloads"]):
            bad.append(f"config {c['name']}: no cell uses it")
        try:
            reference = load_reference(data, bench_root)
        except ValueError as e:
            bad.append(f"config {c['name']}: {e}")
            continue
        for key in unchecked_keys(data, reference):
            bad.append(f"config {c['name']}: {key!r} is checked against "
                       "nothing that runs and is on no list of "
                       "descriptive keys")
    readers = discover_layer_metrics(bench_root)
    for w in bench["workloads"]:
        name_ok("traffic", w["traffic"])
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
            continue
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        try:
            cell = load_cell(w["name"], bench, bench_root)
        except (OSError, KeyError, ValueError) as e:
            bad.append(f"cell {w['name']}: {e}")
            continue
        want = set(cell.traffic.get("end_to_end", ())) | {"setup_s"}
        if set(cell.end_to_end) != want:
            bad.append(f"cell {w['name']}: reports {sorted(cell.end_to_end)}"
                       f", its traffic file says {sorted(want)}")
        if len(cell.end_to_end) < 2 or not cell.per_layer:
            bad.append(f"cell {w['name']}: needs setup_s, another "
                       "end-to-end metric and a per-layer metric")
    for m in bench["per_layer"]:
        reader = readers.get(m["name"])
        if reader is None:
            bad.append(f"{m['name']}: no benchmark/layer_metrics file")
            continue
        for key, attr in (("layer", "LAYER"), ("unit", "UNIT"),
                          ("better", "BETTER"), ("moves", "MOVES"),
                          ("source", "SOURCE")):
            if getattr(reader, attr, None) != m[key]:
                bad.append(f"{m['name']}: {key} {m[key]!r} in "
                           f"BENCHMARK.json, {getattr(reader, attr, None)!r}"
                           " in its file")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for cname in m.get("workloads", cells):
            if cname not in cells:
                bad.append(f"{m['name']}: unknown cell {cname}")
            elif m["moves"] not in _metrics_for(bench["end_to_end"], cname):
                bad.append(f"{m['name']}: cell {cname} does not report "
                           f"{m['moves']}")
    for m in bench["end_to_end"]:
        for cname in m.get("workloads", ()):
            if cname not in cells:
                bad.append(f"{m['name']}: unknown cell {cname}")
    return bad
