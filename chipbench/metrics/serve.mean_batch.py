"""Requests per batch over the window's batches."""

from chipbench.stats import mean


def read(run):
    return mean(len(b.rids) for b in run.batches)
