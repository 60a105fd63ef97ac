"""Mean ``serve/execute`` time per batch (plan done to results on the
host)."""

from chipbench.stats import mean


def read(run):
    return mean((b.t_execute - b.t_plan) * 1e3 for b in run.batches)
