"""Tiny cells for the CPU tests of the harness: the same drivers, readers and
references as the chip's cells, at sizes a test run can hold."""

import copy
import json
import os
import shutil

import chipbench
from chipbench import manifest

RESNET = {
    "name": "resnet50-tiny", "builder": "zoo.ResNet50",
    "reference": "resnet50", "image_size": 64, "num_channels": 3,
    "num_classes": 10, "param_dtype": "float32", "compute_dtype": "float32",
    "per_chip_batch": 8, "control": "fp8",
    "limits": {"loss_gap": 0.1, "grad_norm_gap": 0.02,
               "big_leaf_size": 4096, "big_grad_norm_gap": 0.02,
               "grad_angle": {"fc_w": 0.01, "res3a_b_conv": 0.01,
                              "res4a_b_conv": 0.01},
               "change_norm_gap": 0.15},
}
BERT = {
    "name": "bert-tiny", "builder": "zoo.Bert.large",
    "reference": "bert_decoder", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 128, "vocab_size": 211,
    "max_position_embeddings": 64, "type_vocab_size": 2,
    "param_dtype": "float32", "kv_dtype": "float32", "kv_block_size": 8, "control": "bfloat16",
    "limits": {"logit_gap_max": 1e-4},
}
MIXES = {
    "tiny-staged": {"kind": "staged_ring", "ring": 4, "steps_ahead": 3},
    "tiny-closed": {
        "kind": "closed_loop", "clients": 3, "trace_seed": 5,
        "prompt_tokens": {"dist": "uniform", "min": 20, "max": 40},
        "max_new_tokens": 4, "max_requests_per_s": 200, "shuffle_block": 3,
        "buckets": "batch=1,2,4;seq=32", "warm_prompt_lengths": [32, 40],
        "max_wait_ms": 5.0, "queue_limit": 64, "grace_s": 30,
        "check_requests": 6},
    "tiny-open": {
        "kind": "open_loop", "clients": 16, "trace_seed": 5,
        "rate_per_s": 20.0,
        "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 32},
        "max_new_tokens": 6, "buckets": "batch=1,2,4,8;seq=16,32",
        "warm_prompt_lengths": [16, 32], "max_wait_ms": 5.0,
        "queue_limit": 64, "grace_s": 30, "check_requests": 6},
}
CELLS = [
    {"name": "tiny-fit-staged", "config": "resnet50-tiny",
     "traffic": "tiny-staged", "chips": 1, "why": "test"},
    {"name": "tiny-doc-closed", "config": "bert-tiny",
     "traffic": "tiny-closed", "chips": 1, "why": "test"},
    {"name": "tiny-chat-open", "config": "bert-tiny",
     "traffic": "tiny-open", "chips": 1, "why": "test"},
]
SAME_AS = {"tiny-fit-staged": "resnet50-fit-staged",
           "tiny-doc-closed": "bertL-doc-closed",
           "tiny-chat-open": "bertL-chat-open"}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 8}


def quiet_cache(monkeypatch):
    """Keep JAX's persistent cache out of the tests: entries written here
    cannot be read back without complaint."""
    from deeplearning4j_tpu.util import compile_cache

    monkeypatch.setattr(compile_cache, "enable_persistent_cache",
                        lambda **kw: None)


def tiny_tree(tmp_path, monkeypatch):
    """A copy of the benchmark in ``tmp_path`` with the tiny cells added AS
    FILES AND MANIFEST ENTRIES ONLY, and the harness pointed at it."""
    root = str(tmp_path)
    here = os.path.join(root, "chipbench")
    shutil.copytree(os.path.dirname(chipbench.__file__), here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = copy.deepcopy(manifest.load_manifest())
    for cfg in (RESNET, BERT):
        path = f"chipbench/configs/{cfg['name']}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        man["configs"].append({"name": cfg["name"], "source": "test",
                               "file": path, "reduced": [], "why": "test"})
    for name, mix in MIXES.items():
        with open(os.path.join(here, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    man["workloads"] += CELLS
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "workloads" in m:
                m["workloads"] += [t for t, big in SAME_AS.items()
                                   if big in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    monkeypatch.setattr(manifest, "ROOT", root)
    monkeypatch.setattr(manifest, "HERE", here)
    return man
