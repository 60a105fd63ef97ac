"""A run whose timed path is broken underneath comes out not correct: an
answer altered where the engine produces it, and requests that never get
served.  In-process on the CPU at a tiny size, past the chip check."""

from __future__ import annotations

import jax
import pytest

from chipbench import run
from conftest import args, shrink


def _alter_and(res):
    return [r[:-1] if len(r) else r for r in res]


@pytest.mark.parametrize("name,mode,alter,number", [
    ("gov2.and.saturated", "and", _alter_and, "and_wrong"),
])
def test_an_answer_altered_where_produced_is_caught(bench_copy, monkeypatch,
                                                    name, mode, alter,
                                                    number):
    from repro.index.engine import QueryEngine
    real = QueryEngine.execute

    def broken(self, plan):
        out = real(self, plan)
        return alter(out) if plan.mode == mode else out

    cell = bench_copy.cell(name)
    cfg, traffic = shrink(bench_copy.config(cell["config"]),
                          bench_copy.traffic(cell["traffic"]))
    real_start = run.serve_window

    async def serve_then_break(engine, *a, **k):
        # warm-up runs sound; the window's answers are altered
        monkeypatch.setattr(QueryEngine, "execute", broken)
        return await real_start(engine, *a, **k)

    monkeypatch.setattr(run, "serve_window", serve_then_break)
    res = run.run_cell(bench_copy, cell, cfg, traffic, args(5),
                       jax.devices())
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_requests_shed_are_caught(bench_copy):
    cell = bench_copy.cell("gov2.and.saturated")
    cfg, traffic = shrink(bench_copy.config(cell["config"]),
                          bench_copy.traffic(cell["traffic"]))
    cfg["deadline_ms"] = 1e-6
    res = run.run_cell(bench_copy, cell, cfg, traffic, args(6),
                       jax.devices())
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
    assert res["metrics"]["qps"]["value"] == 0
