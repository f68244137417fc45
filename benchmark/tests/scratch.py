"""A scratch benchmark root for the tests: the real ``benchmark/`` directory
by a symbolic link, and beside it a second directory of ``paths`` that holds
only *new files* — tiny test-only configurations, traffic mixes and one
per-layer metric — with a ``BENCHMARK.json`` that lists them as new entries.
No file of the benchmark is edited, which is what a later PR is held to."""

import json
import os

from benchmark.tests.conftest import ROOT

EXTRA_METRIC = '''"""A metric that only this scratch root has: steps per log period."""


def read(record):
    b = record["boundaries"]
    return (b[-1][0] - b[0][0]) / (len(b) - 1) if len(b) > 1 else None
'''


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def make_root(tmp) -> str:
    tmp = str(tmp)
    os.symlink(os.path.join(ROOT, "benchmark"), os.path.join(tmp, "benchmark"))
    for sub in ("configs", "traffic", "layers"):
        os.makedirs(os.path.join(tmp, "extra", sub))

    def write(sub, name, doc):
        with open(os.path.join(tmp, "extra", sub, name), "w") as f:
            f.write(doc if isinstance(doc, str) else json.dumps(doc))

    gpt = _load("configs", "gpt2-medium.json")
    gpt.update(n_embd=64, n_layer=2, n_head=2, n_inner=128, n_positions=64,
               vocab_size=503)          # ragged against every tile, like 50,257
    gpt["assumed"]["n_inner"] = 128
    write("configs", "tiny-gpt.json", gpt)
    bert = _load("configs", "bert-large.json")
    bert.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
                intermediate_size=128, max_position_embeddings=64,
                vocab_size=503)
    write("configs", "tiny-bert.json", bert)
    write("traffic", "tiny-accum.json", dict(
        _load("traffic", "pretrain-1k.json"), seq_len=32, micro_batch=2,
        accumulation=2))         # log_every as the cell has it: every step
    write("traffic", "tiny-dp4.json", dict(
        _load("traffic", "dp4-sync.json"), seq_len=32, micro_batch=2,
        log_every=4))
    write("traffic", "tiny-mlm.json", dict(
        _load("traffic", "pretrain-128.json"), seq_len=32, predictions=5,
        micro_batch=4, log_every=2))
    write("layers", "steps_per_boundary.py", EXTRA_METRIC)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["benchmark", "extra"]
    bench["configs"] = [
        {"name": "tiny-gpt", "source": "test only", "reduced": [],
         "file": "extra/configs/tiny-gpt.json", "why": "test only"},
        {"name": "tiny-bert", "source": "test only", "reduced": [],
         "file": "extra/configs/tiny-bert.json", "why": "test only"}]
    bench["workloads"] = [
        {"name": "tiny-gpt-accum", "config": "tiny-gpt", "traffic": "tiny-accum",
         "chips": 1, "why": "test only"},
        {"name": "tiny-gpt-dp4", "config": "tiny-gpt", "traffic": "tiny-dp4",
         "chips": 4, "why": "test only"},
        {"name": "tiny-bert-mlm", "config": "tiny-bert", "traffic": "tiny-mlm",
         "chips": 1, "why": "test only"}]
    for metric in bench["per_layer"]:
        metric.pop("workloads", None)
    bench["per_layer"].append(
        {"name": "steps_per_boundary", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "loop and runner",
         "moves": "tokens_per_s_per_chip", "workloads": ["tiny-bert-mlm"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
