"""A small benchmark tree for the CPU tests: the repository's harness,
metric readers and references, with test configurations and mixes."""
from __future__ import annotations

import json
import pathlib
import shutil

from bench.harness import spec

DATA = pathlib.Path(__file__).resolve().parent / "data"

TINY_BENCH = {
    "command": ["python3", "bench/run.py"],
    "paths": ["bench"],
    "run_seconds": 1,
    "configs": [{"name": "tiny-2of4", "source": "test",
                 "file": "bench/configs/tiny-2of4.json", "reduced": [],
                 "why": "test"}],
    "workloads": [
        {"name": "tiny.train", "config": "tiny-2of4", "traffic": "tiny-train",
         "chips": 1, "why": "test"},
    ],
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.train"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"},
    ],
    "per_layer": [
        {"name": "mfu.train", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "train step",
         "moves": "train_tokens_per_s", "workloads": ["tiny.train"]},
    ],
}


def make_root(tmp: pathlib.Path, bench: dict = TINY_BENCH) -> pathlib.Path:
    """A checkout-like tree under ``tmp``: ``BENCHMARK.json`` and
    ``bench/`` with the test configurations and mixes."""
    b = tmp / "bench"
    for sub in ("metrics", "references"):
        shutil.copytree(spec.BENCH_DIR / sub, b / sub)
    shutil.copytree(DATA / "configs", b / "configs")
    shutil.copytree(DATA / "mixes", b / "mixes")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
