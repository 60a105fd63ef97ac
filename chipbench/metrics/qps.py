"""Requests answered inside the window, over the window's length."""


def read(run):
    end = run.t0 + run.seconds
    return sum(r.served and r.t_done <= end for r in run.records) / run.seconds
