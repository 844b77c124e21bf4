"""Smoke-width copies of the benchmark's configurations and mixes, for the
CPU tests: every width shrunk, every rule (menus, limits, lengths'
shapes) taken from the real files."""
from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def lm_config(layers: int = 2) -> dict:
    c = copy.deepcopy(load("configs", "qwen3_4b"))
    c["hf_config"].update(hidden_size=64, intermediate_size=128,
                          num_hidden_layers=layers, num_attention_heads=4,
                          num_key_value_heads=2, head_dim=16,
                          vocab_size=512)
    c["serve"] = {"n_slots": 4, "prefill_len": 32, "max_len": 64,
                  "decode_block": 4}
    return c


def lm_mix(name: str = "poisson_int8") -> dict:
    t = copy.deepcopy(load("traffic", name))
    t.update(prompt={"median": 12, "sigma": 0.9, "min": 4, "max": 32},
             output={"median": 8, "sigma": 0.9, "min": 2, "max": 32},
             trace_s=1.0)
    if t["kind"] == "open_loop":
        t["rate_per_s"] = 3.0
    else:
        t["drain_s"] = 30
    return t


def cnn_config(image: int = 32) -> dict:
    """The ResNet18 table squeezed to ``image`` pixels by the program's
    own rescaling rule (kernels shrink where the map is smaller)."""
    import jax
    from repro.models import cnn
    c = copy.deepcopy(load("configs", "resnet18"))
    _, layers = cnn.init_cnn("resnet18", jax.random.PRNGKey(0), image=image)
    tab = []
    for l in layers:
        d = {"name": l.name, "kind": l.kind}
        if l.kind == "conv":
            d.update(hin=l.hin, cin=l.cin, hk=l.hk, cout=l.cout,
                     stride=l.stride, pad=l.pad, relu=l.relu)
        elif l.kind in ("maxpool", "avgpool"):
            d.update(hin=l.hin, cin=l.cin, hk=l.hk, stride=l.stride,
                     pad=l.pad)
        elif l.kind == "fc":
            d.update(cin=l.cin, cout=l.cout, relu=l.relu)
        else:
            d.update(hin=l.hin, cin=l.cin)
        tab.append(d)
    c.update(layers=tab, image=image, serve={"max_batch": 10})
    return c


def cnn_mix() -> dict:
    t = copy.deepcopy(load("traffic", "hawq_batches"))
    t.update(batch=10, pool_batches=2, trace_s=1.0)
    return t


def bench_file(tmp_path, configs: dict, traffic: dict) -> str:
    """A benchmark file in ``tmp_path``: ``tests/data/cells.json`` (the
    cells and metrics of ``BENCHMARK.json`` and the cells the CPU tests
    also run: the kinds' other mixes), with the named configurations'
    files replaced by the given dicts and the named cells' traffic by
    other mix names."""
    bm = load("tests/data", "cells")
    for c in bm["configs"]:
        if c["name"] in configs:
            path = os.path.join(str(tmp_path), c["name"] + ".json")
            with open(path, "w") as f:
                json.dump(configs[c["name"]], f)
            c["file"] = path
    for w in bm["workloads"]:
        w["traffic"] = traffic.get(w["name"], w["traffic"])
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bm, f)
    return path


class traffic_files:
    """Write mixes into bench/traffic/ under the given names for the
    length of a ``with`` block (the harness finds mixes by name there)."""

    def __init__(self, mixes: dict):
        self.paths = {os.path.join(BENCH, "traffic", k + ".json"): v
                      for k, v in mixes.items()}

    def __enter__(self):
        for p, v in self.paths.items():
            with open(p, "w") as f:
                json.dump(v, f)
        return self

    def __exit__(self, *exc):
        for p in self.paths:
            if os.path.exists(p):
                os.remove(p)
