"""``BENCHMARK.json`` against the format its readers rely on, the harness
finding cells, mixes and metrics by name, and the refusal to run off a
TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run as bench_run
from conftest import BENCH, ROOT, Args

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_and_units_use_only_the_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in SPEC["workloads"]])
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len({w["name"] for w in SPEC["workloads"]}) == len(
        SPEC["workloads"])


def test_entries_have_just_their_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_metric_has_a_reader():
    for m in METRICS:
        assert (BENCH / "metrics" / f"{m['name'].split('.')[0]}.py"
                ).is_file(), m["name"]


def test_moves_target_is_reported_by_each_cell():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    for w in cells.values():
        e2e = {m["name"] for m in bench_run.cell_metrics(SPEC, w, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench_run.cell_metrics(SPEC, w, True)
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for m in SPEC["per_layer"]:
        for w in m["workloads"]:
            assert w in cells


def test_added_files_are_found_by_name(checkout):
    """A configuration, a mix and a metric added as files, with entries
    in BENCHMARK.json, run with no edit to the harness."""
    (checkout / "metrics" / "served_requests.py").write_text(
        "def read(run):\n"
        "    return sum(1 for r in run.records if r.finish == 'length')\n")
    spec = json.loads((checkout.parent / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "served_requests", "unit": "count",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock"})
    (checkout.parent / "BENCHMARK.json").write_text(json.dumps(spec))
    out = bench_run.execute(Args(seed=2**33 + 1, seconds=4.0),
                            bench_dir=checkout, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["served_requests"]["value"] > 0
    assert {"ttft_p95_ms", "itl_p95_ms", "setup_s"} <= set(out["metrics"])
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_exits_non_zero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    res = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no tpu device" in res.stderr


def test_exits_non_zero_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "the program's sources are not at" in res.stderr


def test_nothing_compiles_inside_the_window(checkout):
    """Set-up warms every program the traffic drives, prefix hits and
    resumed prompts included: the window compiles nothing.  (Requests
    cancelled at the close may compile small eager updates while the
    server drains, after the window.)"""
    import time

    from jax._src import monitoring

    _, run, _ = bench_run.start(Args().workload, 11, 6.0, False,
                                bench_dir=checkout, require_tpu=False)
    _, _, engine = bench_run.build(run)
    compiles = []

    def listen(event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(time.monotonic())

    monitoring.register_event_duration_secs_listener(listen)
    try:
        bench_run.measure(run, engine)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert run.counters["prefix_skipped_tokens"] > 0
    assert sum(len(r.tokens) for r in run.records) > 0
    assert [t for t in compiles if run.t0 <= t <= run.end] == []
