"""Tiny cells for the CPU rehearsals: the smoke-sized starcoder2 the
program runs under ``smoke=True``, and traffic cut to match."""

from __future__ import annotations

import copy
from typing import Any, Dict

from bench import harness

CONFIG: Dict[str, Any] = {
    "arch": "starcoder2-3b", "family": "dense", "reference": "starcoder2",
    "smoke": True,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 1,
    "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
    "vocab_pad_multiple": 256, "sliding_window": 64,
    "num_hidden_layers": 4, "rope_theta": 999999.0, "norm_epsilon": 1e-06,
    "dtype": "float32", "chips": 1, "mesh": {"data": 1, "model": 1},
    "train": {"global_batch": 2, "seq_len": 64, "remat": "none"},
    "optimizer": {"name": "adamw", "lr": 1e-3, "warmup": 1,
                  "schedule_steps": 1000, "lr_floor": 0.1, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1,
                  "decay_min_rank": 2, "clip_norm": 1.0,
                  "moment_dtype": "float32"},
    # float32 throughout: the program reads 1e-7 to 3e-5 here, the fp8
    # control 2e-3 to 0.1 on grad_err
    "limits": {"lake_bad_blocks": 0, "loss_gap": 1e-5, "grad_gap": 1e-4,
               "grad_err": 1e-3, "change_gap": 2e-4, "prompt_kept": 0,
               "logit_gap": 1e-3},
}

CORPUS = {"total_tokens": 30000, "median_tokens": 96, "sigma": 1.0,
          "min_tokens": 16, "max_tokens": 1024, "langs": 4, "lang_zipf": 1.0,
          "repo_mean_docs": 4, "layout_seed": 0}

TRAFFIC = {
    "lake": {"kind": "train", "corpus": CORPUS,
             "storage": {"kind": "s3", "latency_s": 0.001,
                         "bandwidth_bps": 1e9, "time_scale": 1.0,
                         "lru_fraction": 0.25},
             "view": {"tql": "SELECT * FROM dataset WHERE lang IN [0, 1]",
                      "langs": [0, 1], "shuffle": True},
             "check_steps": 3, "trace_seconds": 1},
    "staged": {"kind": "train",
               "corpus": dict(CORPUS, min_tokens=130, langs=1,
                              repo_mean_docs=1),
               "storage": {"kind": "memory"},
               "view": {"tql": None, "langs": None, "shuffle": False},
               "check_steps": 3, "trace_seconds": 1},
    "serve": {"kind": "serve", "clients": 4, "prompt_tokens": 16,
              "new_tokens": 8,
              "corpus": dict(CORPUS, total_tokens=8000, min_tokens=16),
              "check_requests": 64, "trace_seconds": 1},
}

E2E = {"train": ["train_tokens_per_s", "train_step_ms_p90", "setup_s"],
       "serve": ["serve_tokens_per_s", "setup_s"]}


#: the tiny model trained FSDP over four devices, a row of the batch each
FSDP4 = dict(CONFIG, chips=4, mesh={"data": 4, "model": 1},
             train=dict(CONFIG["train"], global_batch=4))


def cell(traffic: str, config: Dict[str, Any] = CONFIG) -> harness.Cell:
    t = copy.deepcopy(TRAFFIC[traffic])
    return harness.Cell(
        name=f"tiny-{traffic}", chips=config["chips"],
        config=copy.deepcopy(config), traffic=t, traffic_name=traffic,
        end_to_end=[{"name": n, "unit": "u"} for n in E2E[t["kind"]]],
        per_layer=[], reference=harness.reference_for(config))
