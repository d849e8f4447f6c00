"""Fixtures of the benchmark's own tests: the benchmark's directory on the
path, and a checkout holding a tiny cell that the CPU serves in seconds.

Nothing here loads the TPU library: every test runs on the CPU."""

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_CELL = "tiny.tiny_chat"


def tiny_benchmark() -> dict:
    """``BENCHMARK.json`` with one cell: the tiny configuration under the
    tiny chat mix, reporting what the chat cells report."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "bench/testdata/tiny.json",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "a size the CPU serves in seconds"}]
    bench["workloads"] = [{"name": TINY_CELL, "config": "tiny",
                           "traffic": "tiny_chat", "chips": 1,
                           "why": "the chat mix at a tiny size"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            chat = any(w.endswith(".chat") for w in m["workloads"])
            m["workloads"] = [TINY_CELL] if chat else []
    return bench


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark beside the program's sources, with the
    tiny configuration and mix added as files; returns its ``bench``
    directory.  The compile cache stays off, and the settings a run
    changes are put back."""
    import jax

    dst = tmp_path / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    shutil.copy(BENCH / "testdata" / "tiny.json", dst / "configs" / "tiny.json")
    shutil.copy(BENCH / "testdata" / "tiny_chat.json",
                dst / "traffic" / "tiny_chat.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(tiny_benchmark()))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield dst
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


class Args:
    def __init__(self, workload=TINY_CELL, seed=5, seconds=5.0, trace=0):
        self.workload, self.seed = workload, seed
        self.seconds, self.trace = seconds, trace
