"""Mean wait from enqueue to batch close of the window's served requests
(the server's ``TraceRecord`` stamps)."""

from chipbench.stats import mean


def read(run):
    return mean((t.t_close - t.t_enqueue) * 1e3 for t in run.traces
                if t.t_close is not None and t.outcome == "served")
