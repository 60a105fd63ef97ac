"""Time spent tracing, lowering and compiling programs per batch of the
window: the union of the engine tracer's ``jax/trace``, ``jax/lower`` and
``jax/compile`` spans (one nested in another counts once).  With no
program compiled in the window it is 0; None where programs were compiled
but the program records no compile spans."""

NAMES = ("jax/trace", "jax/lower", "jax/compile")


def read(run):
    if not run.batches:
        return None
    iv = sorted((s.t0, s.t1) for s in run.spans if s.name in NAMES)
    if not iv and run.compiles:
        return None
    total, end = 0.0, float("-inf")
    for a, b in iv:
        a = max(a, end)
        if b > a:
            total, end = total + b - a, b
    return total * 1e3 / len(run.batches)
