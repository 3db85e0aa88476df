"""Toy-width cells for the CPU: the dry addition the contract asks for.  A
directory of NEW files (two configurations, two cells) plus NEW manifest
entries run through the unedited harness."""

from __future__ import annotations

import copy
import json
import os

from benchmarks import arch as A
from benchmarks import harness

TOY_GPT2 = {
    "name": "toy-gpt2", "family": "gpt2", "source": "test",
    "n_embd": 64, "n_head": 4, "n_layer": 2, "n_inner": 256,
    "n_positions": 64, "vocab_size": 211, "layer_norm_epsilon": 1e-5,
    "reduced": [],
}
TOY_MISTRAL = {
    "name": "toy-mistral", "family": "mistral", "source": "test",
    "hidden_size": 64, "intermediate_size": 192, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "sliding_window": 24,
    "max_position_embeddings": 512, "vocab_size": 211, "reduced": [],
}
TOY_TRAIN = {
    "name": "toy.train", "config": "toy-gpt2", "traffic_name": "toytrain",
    "chips": 1, "runner": "train", "mesh": [["data", 1]],
    "traffic": {"kind": "train", "seq": 64, "batch_per_data_group": 2,
                "remat": "flash", "xent_chunk": 32},
    "optimizer": {"learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 1e-4},
    "check_steps": 3, "loss_band_nats": 1.5,
    "limits": {"loss_gap": 0.05, "grad_norm_gap": 0.007, "update_norm_gap": 0.035},
}
TOY_SERVE = {
    "name": "toy.decode", "config": "toy-mistral", "traffic_name": "toydecode",
    "chips": 1, "runner": "serve",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 16, "max_ctx": 64},
    "traffic": {"kind": "closed_loop", "clients": 8, "first_wave": 4,
                "population": 64, "population_seed": 5,
                "prompt_len": {"dist": "uniform", "lo": 4, "hi": 30},
                "output_len": {"dist": "log_uniform", "lo": 4, "hi": 16}},
    "limits": {"served_logit_gap": 0.15},
}
TOY_CHAT = {
    "name": "toy.chat", "config": "toy-gpt2", "traffic_name": "toychat",
    "chips": 1, "runner": "serve",
    "engine": {"num_slots": 4, "block_size": 16, "chunk": 16, "max_ctx": 64},
    "traffic": {"kind": "open_loop", "population": 400, "population_seed": 7,
                "rate_per_s": 40.0, "arrive_share": 0.85,
                "arrival_gap": {"dist": "exponential"},
                "prompt_len": {"dist": "log_normal", "median": 12,
                               "sigma": 0.6, "lo": 4, "hi": 40},
                "output_len": {"dist": "log_normal", "median": 6,
                               "sigma": 0.5, "lo": 2, "hi": 16},
                "max_total": 64},
    "limits": {"served_logit_gap": 0.06},
}


def toy_benchmark(tmp_path):
    """Write the toy files into ``tmp_path`` and return the manifest that
    names them beside everything BENCHMARK.json already has."""
    for sub, files in (("configs", (TOY_GPT2, TOY_MISTRAL)),
                       ("workloads", (TOY_TRAIN, TOY_SERVE, TOY_CHAT))):
        os.makedirs(tmp_path / sub, exist_ok=True)
        for f in files:
            (tmp_path / sub / f"{f['name']}.json").write_text(json.dumps(f))
    manifest = copy.deepcopy(harness.load_manifest())
    manifest["workloads"] += [
        {"name": "toy.train", "config": "toy-gpt2", "traffic": "toytrain",
         "chips": 1, "why": "test"},
        {"name": "toy.decode", "config": "toy-mistral", "traffic": "toydecode",
         "chips": 1, "why": "test"},
        {"name": "toy.chat", "config": "toy-gpt2", "traffic": "toychat",
         "chips": 1, "why": "test"}]
    manifest["end_to_end"] += [
        {"name": "ttft_p90_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["toy.chat"]},
        {"name": "gap_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["toy.chat"]}]
    manifest["per_layer"].append(
        {"name": "queue_wait_p90_ms.chat", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "serving scheduler",
         "moves": "ttft_p90_ms", "workloads": ["toy.chat"]})
    os.makedirs(tmp_path / "layer_metrics", exist_ok=True)
    (tmp_path / "layer_metrics" / "queue_wait_p90_ms.chat.json").write_text(
        json.dumps({"name": "queue_wait_p90_ms.chat", "unit": "ms",
                    "reader": "span_percentile",
                    "args": {"span": "queue_wait", "q": 90, "scale": 1000.0}}))
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tok_s_chip":
            m["workloads"].append("toy.train")
        if m["name"] == "serve_tok_s":
            m["workloads"].append("toy.decode")
    return manifest
