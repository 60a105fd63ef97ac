"""2-stage GPipe pipeline parallelism across the "pod" axis (DESIGN §8).

The multi-pod mesh's "pod" axis defaults to data parallelism; for models too
deep/large for one pod, this module instead splits the layer stack in two
stages and microbatches activations across pods via collective-permute —
the inter-pod hop is the only DCN traffic, once per microbatch, overlapping
with the other pod's compute (GPipe schedule, bubble = 1/(n_micro+1)).

SPMD formulation: stacked layer params are sharded on the layer dim over
"pod" (each pod materializes only its half); both pods run the same program;
`ppermute` forwards stage-0 outputs to stage 1 one step delayed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_2stage(layer_fn, params_stacked, x_micro, mesh, *, pod_axis="pod"):
    """Run x through L stacked layers split across 2 pods.

    layer_fn(lp, x) -> x              (one layer)
    params_stacked: pytree, leaves (L, ...) with L even
    x_micro: (n_micro, mb, ...) microbatched input (replicated over pod)
    Returns (n_micro, mb, ...) outputs after all L layers.
    """
    n_micro = x_micro.shape[0]

    def local(params_local, xm):
        # params_local leaves: (L/2, ...) — this pod's stage
        me = jax.lax.axis_index(pod_axis)

        def run_stage(x):
            def body(h, lp):
                return layer_fn(lp, h), None
            h, _ = jax.lax.scan(body, x, params_local)
            return h

        zero = jnp.zeros_like(xm[0])

        def step(buf, t):
            # stage 0 consumes microbatch t (valid for t < n_micro);
            # stage 1 consumes the buffer received from stage 0.
            inp = jnp.where(me == 0, xm[jnp.minimum(t, n_micro - 1)], buf)
            out = run_stage(inp)
            sent = jax.lax.ppermute(out, pod_axis, [(0, 1), (1, 0)])
            return sent, out

        _, outs = jax.lax.scan(step, zero, jnp.arange(n_micro + 1))
        # stage-1 outputs for steps 1..n_micro are the pipeline results
        return outs[1:]

    pspecs = jax.tree.map(lambda _: PS(pod_axis), params_stacked)
    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspecs, PS()),
        out_specs=PS(pod_axis),       # (2*n_micro, ...) stacked by pod
        axis_names=frozenset({pod_axis}),
        check_vma=False,
    )
    out = mapped(params_stacked, x_micro)
    # pod 1's block holds the completed microbatches
    return out[n_micro:]
