"""The harness is driven by data: a cell, its configuration, traffic and
metrics are found by name, and a new one needs new files only.  Runs
in-process on the CPU at a tiny size; the command itself needs a TPU."""

from __future__ import annotations

import json
import os

import jax
import pytest

from chipbench import run, trace_reduce
from conftest import ROOT, args, shrink

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _stub_trace(monkeypatch):
    """The CPU has no TPU plane to reduce: stand in a fixed summary."""
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *a, **k:
                        trace_reduce.Summary(0.25, 1.5, {"jit_x": 0.25},
                                             {"serve/execute": 1.25}, 1))


def _run(bench, name, seed, **kw):
    cell = bench.cell(name)
    cfg, traffic = shrink(bench.config(cell["config"]),
                          bench.traffic(cell["traffic"]))
    a = args(seed)
    for k, v in kw.items():
        setattr(a, k, v)
    return run.run_cell(bench, cell, cfg, traffic, a, jax.devices())


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_runs_correct_at_a_tiny_size(bench_copy, name):
    res = _run(bench_copy, name, seed=2 ** 31 + 7)
    assert res["correct"] is True and res["failed"] == 0
    want = {m["name"] for m in bench_copy.metrics(bench_copy.cell(name),
                                                  traced=False)}
    assert set(res["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1


def test_a_cell_added_as_files_runs_without_editing_code(bench_copy,
                                                         monkeypatch):
    here = bench_copy.here
    with open(os.path.join(here, "configs", "gov2-shard.json")) as f:
        cfg = json.load(f)
    cfg.update(name="wiki-shard", shape="wikipedia", zipf_s=1.25,
               doclen=344)
    with open(os.path.join(here, "configs", "wiki-shard.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(here, "traffic", "or-bursty.json"), "w") as f:
        json.dump({"loop": "open", "arrivals": "gamma", "gamma_shape": 0.25,
                   "rate_qps": 20.0, "modes": {"or": 1.0}, "k": 5,
                   "lengths": {"2": 1.0}, "warm_seconds": 0.5,
                   "check_ranked": 8, "work_seed": 5}, f)
    with open(os.path.join(here, "metrics", "extra.batches.py"), "w") as f:
        f.write("def read(run):\n    return len(run.batches)\n")
    with open(os.path.join(here, "metrics", "p95_ms.py"), "w") as f:
        f.write("from chipbench.stats import latencies_ms, nearest_rank\n\n\n"
                "def read(run):\n"
                "    return nearest_rank(latencies_ms(run.records), 95)\n")
    doc = bench_copy.doc
    doc["configs"].append({"name": "wiki-shard", "source": "test",
                           "file": "chipbench/configs/wiki-shard.json",
                           "reduced": []})
    doc["workloads"].append({"name": "wiki.or.bursty", "config": "wiki-shard",
                             "traffic": "or-bursty", "chips": 1,
                             "why": "test"})
    doc["end_to_end"].append({"name": "p95_ms", "unit": "ms",
                              "better": "lower", "bound": 0.25,
                              "source": "host_clock",
                              "workloads": ["wiki.or.bursty"]})
    doc["per_layer"].append({"name": "extra.batches", "unit": "batches",
                             "better": "higher", "source": "program_counter",
                             "layer": "test", "moves": "p95_ms",
                             "workloads": ["wiki.or.bursty"]})
    with open(os.path.join(bench_copy.root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    from chipbench.spec import Bench
    bench = Bench(bench_copy.root, here)
    _stub_trace(monkeypatch)
    res = _run(bench, "wiki.or.bursty", seed=11)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"p95_ms", "qps", "bits_per_posting",
                                   "setup_s"}
    res = _run(bench, "wiki.or.bursty", seed=11, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["extra.batches"]["value"] >= 1
    assert set(res["metrics"]) == {"extra.batches"}
    assert res["device"]["busy_s"] == 0.25
    assert res["breakdown"]["idle_gaps"] == [["serve/execute", 1.25]]


def test_the_dotted_variant_reads_through_its_stem(bench_copy):
    assert (bench_copy.reader("serve.queue_ms.sat")
            is bench_copy.reader("serve.queue_ms.sat"))
    with pytest.raises(SystemExit):
        bench_copy.reader("no_such_metric")


def test_the_command_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_the_window_keeps_the_persistent_cache_off(bench_copy, monkeypatch):
    seen = []
    real = run.drive

    async def drive(*a, **k):
        if not seen:                # the first call is the window's
            seen.append(jax.config.jax_enable_compilation_cache)
        return await real(*a, **k)

    monkeypatch.setattr(run, "drive", drive)
    before = jax.config.jax_enable_compilation_cache
    res = _run(bench_copy, CELLS[0], seed=3)
    assert res["correct"] is True
    assert seen == [False]
    assert jax.config.jax_enable_compilation_cache == before
