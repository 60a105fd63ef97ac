"""Mean ``serve/plan`` time per batch (batch close to plan done)."""

from chipbench.stats import mean


def read(run):
    return mean((b.t_plan - b.t_close) * 1e3 for b in run.batches)
