"""Make the benchmark package (``chipbench/`` at the repo root) importable
and give its tests a tiny cell to run on the CPU."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_DOCS = 6000
TINY_LISTS = 24


def shrink(cfg: dict, traffic: dict) -> tuple:
    """A configuration and traffic mix at a size the CPU serves in
    seconds: the same shape, fewer docs and lists, less traffic."""
    cfg = dict(cfg, n_docs=TINY_DOCS, n_lists=TINY_LISTS,
               name="tiny-" + cfg["name"])
    traffic = dict(traffic)
    if traffic["loop"] == "open":
        traffic.update(rate_qps=20.0, warm_seconds=0.5)
    else:
        traffic.update(clients=3, warm_requests=6)
    return cfg, traffic


def args(seed: int, seconds: float = 1.5, control: int = 0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 sweep="", control=control, trace_out="")


@pytest.fixture
def bench_copy(tmp_path):
    """``BENCHMARK.json`` and ``chipbench/`` copied into a temporary root
    (built indexes land there, not in the checkout)."""
    import shutil
    from chipbench import spec
    root = tmp_path / "root"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("built", "traces",
                                                  "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    return spec.Bench(str(root), str(root / "chipbench"))
