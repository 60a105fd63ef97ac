"""Distribution-layer tests on 8 forced host devices (subprocess isolation so
the rest of the suite keeps a single device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str) -> str:
    code = "import os\nos.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n" + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_compressed_allreduce_matches_pmean():
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as PS
    from repro.distributed import collectives as C
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh((8,), ('dp',))
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8*5000,)).astype(np.float32))
    def f(xl):
        red, ef = C.compressed_allreduce_flat(xl.reshape(-1), ('dp',), bits=8)
        return red, ef
    red, ef = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=PS('dp'),
                                    out_specs=(PS(None), PS('dp')), check_vma=False))(x)
    exact = np.mean(np.asarray(x).reshape(8, 5000), axis=0)
    err = np.abs(np.asarray(red)[:5000] - exact)
    assert err.max() < 0.05 * (np.abs(exact).max() + 1e-6), err.max()
    print('OK', err.max())
    """)
    assert "OK" in out


def test_sharded_lm_forward_matches_single_device():
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smollm_135m
    from repro.distributed import sharding as shlib
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    cfg = smollm_135m.make_smoke_config()
    params = T.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (4, 32)), jnp.int32)
    ref, _ = jax.jit(lambda p, t: T.loss_fn(p, t[:, :-1], t[:, 1:], cfg))(params, toks)
    mesh = make_host_mesh((4, 2), ('data', 'model'))
    plan = shlib.lm_dense_plan()
    with shlib.activate(mesh, plan):
        sh, _ = jax.jit(lambda p, t: T.loss_fn(p, t[:, :-1], t[:, 1:], cfg))(params, toks)
    assert abs(float(ref) - float(sh)) < 1e-4, (float(ref), float(sh))
    print('OK', float(ref), float(sh))
    """)
    assert "OK" in out


def test_embedding_ep_lookup_matches_plain():
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed import sharding as shlib
    from repro.launch.mesh import make_host_mesh
    from repro.models import embedding as E
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(0, 1, (64, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (16, 5)), jnp.int32)
    ref = np.asarray(jnp.take(table, ids, axis=0))
    mesh = make_host_mesh((2, 4), ('data', 'model'))
    with shlib.activate(mesh, shlib.recsys_plan()):
        got = np.asarray(jax.jit(E.lookup)(table, ids))
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    # stacked
    tables = jnp.asarray(rng.normal(0, 1, (3, 64, 8)).astype(np.float32))
    ids2 = jnp.asarray(rng.integers(0, 64, (16, 3)), jnp.int32)
    ref2 = np.stack([np.asarray(tables[t])[np.asarray(ids2)[:, t]] for t in range(3)], axis=1)
    with shlib.activate(mesh, shlib.recsys_plan()):
        got2 = np.asarray(jax.jit(E.lookup_stacked)(tables, ids2))
    np.testing.assert_allclose(got2, ref2, rtol=1e-6)
    print('OK')
    """)
    assert "OK" in out


def test_checkpoint_reshard_elastic():
    out = run_py("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PS
    from repro.checkpoint import Checkpointer
    from repro.launch.mesh import make_host_mesh
    mesh8 = make_host_mesh((8, 1), ('data', 'model'))
    mesh4 = make_host_mesh((4, 1), ('data', 'model'))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    x8 = jax.device_put(x, NamedSharding(mesh8, PS('data', None)))
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, {'x': x8}, {'cursor': 5})
        tmpl = {'x': jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        sh = {'x': NamedSharding(mesh4, PS('data', None))}
        state, step, extra = ck.restore(tmpl, shardings=sh)
        assert extra['cursor'] == 5 and step == 1
        np.testing.assert_array_equal(np.asarray(state['x']), np.asarray(x))
        assert state['x'].sharding.mesh.shape['data'] == 4
    print('OK elastic reshard 8->4 devices')
    """)
    assert "OK" in out
