"""A tiny copy of the benchmark for the CPU tests: the real data files plus
one small configuration of each family, one small traffic mix for each loop
driver, and the cells that pair them — added as NEW files and entries, the
way a later PR would add a cell."""
import json
import os
import shutil

from chipbench import manifest

TINY_RESNET = {
    "family": "resnet", "source": "test",
    "builder": "mxnet_tpu.models.resnet:get_symbol",
    "symbol_args": {"num_classes": "num_classes", "num_layers": "num_layers",
                    "image_shape": "image_shape"},
    "reference": "chipbench.reference.resnet50",
    "num_layers": 50, "num_classes": 10, "image_shape": [3, 48, 48],
    "units": [3, 4, 6, 3], "filter_list": [64, 256, 512, 1024, 2048],
    "compute_dtype": "float32", "master_dtype": "float32",
    "check_is_train": True,
}
TINY_LM = {
    "family": "decoder_lm", "source": "test",
    "builder": "mxnet_tpu.models.attention_lm:get_symbol",
    "symbol_args": {"vocab_size": "vocab_size",
                    "seq_len": "max_position_embeddings",
                    "num_layers": "num_hidden_layers", "embed": "hidden_size",
                    "heads": "num_attention_heads", "ffn_hidden": "ffn_dim"},
    "reference": "chipbench.reference.opt",
    "vocab_size": 96, "hidden_size": 32, "ffn_dim": 64,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "max_position_embeddings": 64, "train_num_hidden_layers": 1,
    "compute_dtype": "float32", "master_dtype": "float32",
    "serve_dtype": "float32", "tie": {"head_weight": "embed_weight"},
}
TINY_TRAFFIC = {
    "tiny_closed_img": {
        "driver": "train_fit", "batch": 4, "pool": 2, "warmup_steps": 2,
        "trace_seconds": 1, "check_samples": 2,
        "optimizer": {"name": "sgd", "params": {"learning_rate": 0.01,
                                                "momentum": 0.9}}},
    "tiny_closed_lm": {
        "driver": "train_fit", "batch": 2, "seq_len": 64,
        "layers_key": "train_num_hidden_layers", "pool": 2,
        "warmup_steps": 2, "trace_seconds": 1, "check_tokens": 32,
        "optimizer": {"name": "adam", "params": {"learning_rate": 0.001}}},
    "tiny_backlog": {
        "driver": "serve_ticks", "slots": 4, "cache_len": 64,
        "page_tokens": 8, "prefill_chunk": 8, "max_prefill": 32,
        "kv_dtype": "int8", "prompt_min": 4, "prompt_max": 24,
        "output_min": 4, "output_max": 16, "requests": 512, "block": 16,
        "order_seed": 0,
        "warmup_ticks": 2, "trace_seconds": 1, "trace_ticks": 5,
        "check_prompt": 20,
        "check_decode": 4},
}
TINY_CELLS = [("tiny_rn", "tiny-resnet", "tiny_closed_img"),
              ("tiny_lm", "tiny-lm", "tiny_closed_lm"),
              ("tiny_serve", "tiny-lm", "tiny_backlog")]


def make_root(tmp_path):
    """Copy the benchmark's data into ``tmp_path`` and add the tiny files
    and entries; returns the root's path."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, manifest.HERE),
                    os.path.join(root, manifest.HERE),
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "testdata"))
    man = manifest.load_manifest()
    real = manifest.load_json(manifest.ROOT,
                              "chipbench/configs/resnet50.json")
    real_lm = manifest.load_json(manifest.ROOT,
                                 "chipbench/configs/opt-1.3b.json")
    for name, cfg, like in (("tiny-resnet", TINY_RESNET, real),
                            ("tiny-lm", TINY_LM, real_lm)):
        rel = "chipbench/configs/%s.json" % name
        with open(os.path.join(root, rel), "w") as f:
            json.dump(dict(cfg, init=like["init"], counts=like["counts"],
                           limits=like["limits"]), f)
        man["configs"].append({"name": name, "source": "test", "file": rel,
                               "reduced": [], "why": "CPU test size"})
    for name, traffic in TINY_TRAFFIC.items():
        with open(os.path.join(root, manifest.traffic_path(name)), "w") as f:
            json.dump(traffic, f)
    # a tiny cell reports what the accepted cells of its kind report: a
    # cell's kind is its traffic file's loop driver and its chips
    kind_of = {w["name"]: (manifest.load_json(
        manifest.ROOT, manifest.traffic_path(w["traffic"]))["driver"],
        w["chips"]) for w in man["workloads"]}
    for cell, cfg, traffic in TINY_CELLS:
        man["workloads"].append({"name": cell, "config": cfg,
                                 "traffic": traffic, "chips": 1,
                                 "why": "CPU test size"})
        kind = (TINY_TRAFFIC[traffic]["driver"], 1)
        for m in man["end_to_end"] + man["per_layer"]:
            if kind in {kind_of.get(w) for w in m.get("workloads", ())}:
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root
