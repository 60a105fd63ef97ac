"""95th percentile of how late the load generator submitted a request
after it was due (a starved generator must not read as a fast server)."""

from chipbench.stats import nearest_rank


def read(run):
    late = sorted((r.t_submit - r.t_due) * 1e3 for r in run.records
                  if r.t_submit is not None)
    return nearest_rank(late, 95) if late else None
