"""Share of the window's requests whose batch was planned on the device
(``device`` or ``fused``) rather than on host numpy."""


def read(run):
    n = sum(len(b.rids) for b in run.batches)
    dev = sum(len(b.rids) for b in run.batches
              if b.placement in ("device", "fused"))
    return dev / n if n else None
