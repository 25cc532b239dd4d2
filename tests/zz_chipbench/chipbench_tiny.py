"""A temporary copy of chipbench's data directories with tiny cells added:
what the CPU rehearsals run, and the proof that a new cell needs only new
files (nothing that is there is edited)."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIPBENCH = os.path.join(REPO, "chipbench")
DATA_DIRS = ("cells", "configs", "traffic", "metrics", "readers")

TINY_CONFIG = {
    "name": "gpt2-tiny", "source": "tests only", "reduced": [],
    "serve": {"argv": [
        "--random-init", "--model-preset", "tiny", "--max-len", "96",
        "--max-batch-size", "4", "--max-prefill-len", "32",
        "--prefill-buckets", "16,32", "--kv-block-size", "8",
        "--queue-capacity", "64"]},
}
TINY_CHAT = {
    "name": "tiny-chat", "driver": "serve", "generator": "open_loop",
    "arrivals": {"process": "poisson", "rate_per_s": 8.0},
    "prompt": {"shared_prefix": {"count": 2, "tokens": 16, "zipf_s": 1.0},
               "unique": {"median": 12, "sigma": 0.5, "min": 4, "max": 40}},
    "output": {"median": 5, "sigma": 0.4, "min": 2, "max": 8},
    "max_total": 90, "sampling": {"temperature": 0.0, "top_k": None},
    "warmup": {"seconds": 0.5}, "lateness_limit_share": 10.0,
    "unstarted_limit_share": 1.0, "trace_seconds": 0.5,
}
TINY_GEN = {
    "name": "tiny-gen", "driver": "serve", "generator": "backlog",
    "backlog_depth": 4,
    "prompt": {"unique": {"median": 12, "sigma": 0.5, "min": 4, "max": 30}},
    "output": {"median": 10, "sigma": 0.4, "min": 4, "max": 20},
    "max_total": 90, "sampling": {"temperature": 0.8, "top_k": 40},
    "warmup": {"seconds": 0.3}, "trace_seconds": 0.5,
}
TINY_TRAIN = {
    "name": "tiny-train", "driver": "train", "generator": "train_steps",
    "job": {"argv": ["--config", "gpt2_124m", "--model-preset", "tiny",
                     "--batch-size", "4", "--parallel", "single",
                     "--platform", "cpu"],
            "batch_size": 4, "seq_len": 64, "vocab_size": 512,
            "causal": True, "data": "synthetic_tokens", "reference": "gpt2",
            "reference_block": 2, "steps": 12, "log_every": 2,
            "trace_steps": 2, "loss_rtol": 1e-4, "clock_rtol": 0.5},
}
TINY_BERT = {
    "name": "tiny-bert", "driver": "train", "generator": "train_steps",
    "job": {"argv": ["--config", "bert_base_zero1", "--model-preset", "tiny",
                     "--batch-size", "8", "--parallel", "zero1",
                     "--mesh", "dp=4", "--platform", "cpu"],
            "batch_size": 8, "seq_len": 64, "vocab_size": 512,
            "causal": False, "data": "synthetic_mlm", "mask_rate": 0.15,
            "mask_token": 1, "reference": "bert", "reference_block": 2,
            "steps": 12, "log_every": 2, "trace_steps": 2,
            "loss_rtol": 1e-4, "clock_rtol": 0.5},
}


def _cell(name, traffic, like, chips=1):
    with open(os.path.join(CHIPBENCH, "cells", f"{like}.json")) as f:
        cell = json.load(f)
    cell.update(name=name, config="gpt2-tiny", traffic=traffic, chips=chips,
                why="tests only")
    return cell


def make_root(tmp: str) -> str:
    """Copy the data directories to ``tmp`` and ADD the tiny files."""
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(CHIPBENCH, d), os.path.join(tmp, d))
    files = {
        "configs/gpt2-tiny.json": TINY_CONFIG,
        "traffic/tiny-chat.json": TINY_CHAT,
        "traffic/tiny-gen.json": TINY_GEN,
        "traffic/tiny-train.json": TINY_TRAIN,
        "traffic/tiny-bert.json": TINY_BERT,
        "cells/tiny.chat.json": _cell("tiny.chat", "tiny-chat",
                                      "gpt2-124m.chat-prefix"),
        "cells/tiny.gen.json": _cell("tiny.gen", "tiny-gen",
                                     "gpt2-124m.batch-gen"),
        "cells/tiny.train.json": _cell("tiny.train", "tiny-train",
                                       "gpt2-124m.train"),
        "cells/tiny.bert.json": _cell("tiny.bert", "tiny-bert",
                                      "bert-base.train-zero1-x4", chips=4),
    }
    for rel, obj in files.items():
        path = os.path.join(tmp, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)
    return tmp
