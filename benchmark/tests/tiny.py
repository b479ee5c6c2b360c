"""A tiny benchmark laid over the shipped one, for the CPU rehearsals: its
own ``BENCHMARK.json``, configurations and traffic files in a temporary
directory (the harness looks there first and finds everything else, the
readers, builders and references, in the shipped directory).  This is also
how a later PR rehearses a cell it adds: new files, no edit.

The overlay also carries the three cells ``BENCHMARK.json`` does not list yet
(PERF.md, Open questions): the GPT training cell, a serving cell over the
``lm_serve`` builder and the four-chip data-parallel cell, with the entries
and the traffic and metric files the shipped directory lacks, so that the
harness they need stays rehearsed."""

from __future__ import annotations

import copy
import json
import os

from benchlib import load_json

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)

TINY_ALEXNET = {
    "input_size": 67, "n_classes": 10, "n_label_classes": 5,
    "minibatch_size": 4,
    "workflow": {"module": "znicz_tpu.models.alexnet", "builder": "build",
                 "kwargs": {"n_classes": 10, "input_size": 67, "lr": 0.01,
                            "dropout": 0.5}},
}
TINY_GPT = {"n_embd": 64, "n_head": 2, "n_inner": 128, "n_layer": 2,
            "n_positions": 64, "vocab_size": 97,
            "builders": {"lm_train": {"loss_chunks": 2},
                         "lm_serve": {"slots": 4, "max_len": 64, "page": 8,
                                      "arena_pages": 40, "max_queue": 64,
                                      "timeout_s": 120.0}}}
TINY_TRAFFIC = {
    "train_hbm": {"n_train": 32, "k_steps": 2},
    "train_hbm_dp4": {"n_train": 64, "k_steps": 2},
    "train_tokens_t2048": {"n_rows": 16, "minibatch_size": 2, "seq_len": 32,
                           "k_steps": 2},
}
#: a serving mix, whole: the shipped directory has none yet
TINY_CHAT = {
    "name": "chat_tiny", "builder": "lm_serve",
    "generator": "open_loop_quantiles", "rate_rps": 6.0,
    "prompt_tokens": {"median": 12, "p95": 30, "lo": 8, "hi": 40,
                      "round_to": 8},
    "output_tokens": {"median": 4, "p95": 8, "lo": 2, "hi": 10,
                      "round_to": 1},
    "sampling": "greedy", "drain_s": 60.0, "check_requests": 3,
    "trace_from_s": 0.2, "trace_seconds": 1.0,
}
GPT_CONFIG = {"name": "cerebras_gpt_1.3b", "source": "rehearsal",
              "file": "benchmark/configs/cerebras_gpt_1.3b.json",
              "reduced": [], "why": "rehearsal"}
GPT_CELL = {"name": "cgpt_train_t2048", "config": "cerebras_gpt_1.3b",
            "traffic": "train_tokens_t2048", "chips": 1, "why": "rehearsal"}
SERVE_CELL = {"name": "cgpt_serve_chat", "config": "cerebras_gpt_1.3b",
              "traffic": "chat_tiny", "chips": 1, "why": "rehearsal"}
DP4_CELL = {"name": "alexnet_train_dp4", "config": "alexnet",
            "traffic": "train_hbm_dp4", "chips": 4, "why": "rehearsal"}
#: name -> (unit, layer, source, reader, params); all move the gap but
#: the two that time a request's wait, which move the time to first token
SERVE_METRICS = {
    "serve_gap_p95_ms": ("ms", "entry", "host_clock", "serve_latency",
                         {"quantity": "gap", "q": 95}),
    "serve_ttft_p95_ms": ("ms", "entry", "host_clock", "serve_latency",
                          {"quantity": "ttft", "q": 95}),
    "serve_queue_ms_p95": ("ms", "serve scheduler", "program_span",
                           "serve_queue", {"q": 95}),
    "serve_batch_occupancy": ("%", "serve scheduler", "program_span",
                              "serve_occupancy", {}),
    "serve_prefill_ms_p50": ("ms", "decode programs", "program_span",
                             "serve_span", {"span": "generate.prefill",
                                            "q": 50}),
    "serve_decode_step_ms_p50": ("ms", "decode programs", "program_span",
                                 "serve_span",
                                 {"span": "generate.decode_step", "q": 50}),
    "loadgen_late_ms_p95": ("ms", "load generator", "host_clock",
                            "loadgen_late", {"q": 95}),
    "collective_ms_per_step": ("ms", "collectives", "device_trace",
                               "collective_ms", {}),
}
SERVE_END_TO_END = ("serve_gap_p95_ms", "serve_ttft_p95_ms")
TTFT_SIDE = ("serve_queue_ms_p95", "serve_prefill_ms_p50",
             "loadgen_late_ms_p95")


def _dump(root: str, kind: str, name: str, doc: dict) -> None:
    os.makedirs(os.path.join(root, kind), exist_ok=True)
    with open(os.path.join(root, kind, name + ".json"), "w") as f:
        json.dump(doc, f)


def write_overlay(root: str) -> str:
    """Write the tiny overlay under ``root``; returns ``root``."""
    bench = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    for kind, tiny in (("configs", {"alexnet": TINY_ALEXNET,
                                    "cerebras_gpt_1.3b": TINY_GPT}),
                       ("traffic", TINY_TRAFFIC)):
        for name, changes in tiny.items():
            doc = copy.deepcopy(load_json(os.path.join(BENCH_DIR, kind,
                                                   name + ".json")))
            doc.update(changes)
            _dump(root, kind, name, doc)
    _dump(root, "traffic", "chat_tiny", TINY_CHAT)
    bench["configs"].append(GPT_CONFIG)
    bench["workloads"] += [GPT_CELL, SERVE_CELL, DP4_CELL]
    for m in bench["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"] += [GPT_CELL["name"], DP4_CELL["name"]]
    for name, unit in (("flash_attn_ms_per_step", "ms"),
                       ("flash_attn_roofline", "%")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_samples_per_s",
            "workloads": [GPT_CELL["name"]]})
    for name, (unit, layer, source, reader, params) in SERVE_METRICS.items():
        _dump(root, "metrics", name, {
            "name": name, "unit": unit, "layer": layer, "source": source,
            "reader": reader, "params": params})
        entry = {"name": name, "unit": unit, "better": "lower",
                 "source": source}
        if name in SERVE_END_TO_END:
            bench["end_to_end"].append(
                {**entry, "bound": 0.1, "workloads": [SERVE_CELL["name"]]})
            continue
        cell, moves = SERVE_CELL["name"], "serve_gap_p95_ms"
        if name in TTFT_SIDE:
            moves = "serve_ttft_p95_ms"
        elif name == "collective_ms_per_step":
            cell, moves = DP4_CELL["name"], "train_samples_per_s"
        bench["per_layer"].append({**entry, "layer": layer, "moves": moves,
                                   "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
