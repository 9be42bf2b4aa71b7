"""Tiny cells for the CPU tests: the benchmark's own cell files with the
model and traffic cut down, so a whole run (set-up, window, reference)
takes seconds on the CPU."""
from __future__ import annotations

import argparse
import copy

from bench import common

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256}


def cell(name: str) -> dict:
    c = copy.deepcopy(common.resolve_cell(name))
    c["config_file"].update(TINY_MODEL)
    t = c["traffic_file"]
    if t["kind"] == "train":
        t.update(seq_len=64, batch=2)
        c["config_file"]["run"]["loss_chunk"] = 32
    else:
        t["prompt"] = dict(t["prompt"], min=8, max=40)
        if "median" in t["prompt"]:
            t["prompt"]["median"] = 16
        t["output"] = dict(t["output"], min=4, max=24, median=8)
        t.update(pre_window_s=0.5, drain_s=5)
        if t["loop"] == "open":
            t["rate_per_s"] = 20.0
        else:
            t.update(clients=4, max_tokens_per_s=400)
        c["settings"].update(max_slots=4, max_len=80, pool_tokens=1024,
                             reference_len=64, reference_rows=24, sample_tokens=40)
    return c


def args(workload: str, seed: int = 12345, seconds: float = 1.0, trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rate=None)
