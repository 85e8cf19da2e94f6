"""Multi-device tests (host devices, run in subprocesses so the main
pytest process keeps its single real device — see conftest note).

Each subprocess gets as many host devices as its mesh uses, and calls
every ``shard_map`` program through ``jax.jit``: called eagerly, a
``shard_map`` compiles each primitive of its body on its own.

All mesh/shard_map construction goes through the wrappers in
``repro.core.compat`` (re-exported by ``repro.distributed.sharding``).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

def _run(code: str, devices: int = 8) -> str:
    """Run ``code`` in a fresh interpreter over ``devices`` host devices,
    well inside the test's own time limit (root conftest.py)."""
    env = {**os.environ, "PYTHONPATH": "src", "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={devices}"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=100,
                       env=env, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


class TestDistributedTables:
    def test_distributed_mode_insert_retrieve(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import distributed as dist
            from repro.core.compat import make_mesh_compat
            mesh = make_mesh_compat((8,), ('x',))
            table = dist.create_sharded(mesh, 'x', 2048, window=16)
            shard_insert = jax.jit(dist.shard_insert, static_argnums=(0, 1))
            shard_retrieve = jax.jit(dist.shard_retrieve,
                                     static_argnums=(0, 1))
            n = 8 * 512
            keys = jnp.asarray(np.random.default_rng(0).permutation(
                np.arange(1, n + 1, dtype=np.uint32)))
            vals = keys * 3
            table, status, ov = shard_insert(mesh, 'x', table, keys, vals)
            assert int(np.asarray(ov).sum()) == 0, 'exchange overflow'
            assert (np.asarray(status) != 2).all()
            got, found, _ = shard_retrieve(mesh, 'x', table, keys)
            assert np.asarray(found).all()
            assert (np.asarray(got) == np.asarray(vals)).all()
            miss, mf, _ = shard_retrieve(
                mesh, 'x', table,
                jnp.arange(n + 10, n + 10 + n, dtype=jnp.uint32))
            assert not np.asarray(mf).any()
            print('OK')
        """)
        assert "OK" in out

    def test_single_owner_invariant(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import distributed as dist
            from repro.core.common import EMPTY_KEY, TOMBSTONE_KEY
            from repro.core.compat import make_mesh_compat
            mesh = make_mesh_compat((8,), ('x',))
            table = dist.create_sharded(mesh, 'x', 1024, window=16)
            keys = jnp.arange(1, 2001, dtype=jnp.uint32)
            table, _, ov = jax.jit(dist.shard_insert, static_argnums=(0, 1))(
                mesh, 'x', table, keys, keys)
            assert int(np.asarray(ov).sum()) == 0
            kp = np.asarray(table.key_planes())[:, 0]   # (8, p, W)
            seen = {}
            for shard in range(8):
                live = kp[shard][(kp[shard] != EMPTY_KEY)
                                 & (kp[shard] != TOMBSTONE_KEY)]
                for k in live.tolist():
                    assert k not in seen, f'key {k} on two shards'
                    seen[k] = shard
            assert len(seen) == 2000
            # owners match hash_owner
            from repro.core import hashing
            owners = np.asarray(hashing.hash_owner(keys, 8))
            for k, o in zip(np.asarray(keys).tolist(), owners.tolist()):
                assert seen[k] == o
            print('OK')
        """)
        assert "OK" in out

    def test_independent_mode(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core import distributed as dist, single_value as sv
            from repro.core.compat import make_mesh_compat, shard_map_compat
            mesh = make_mesh_compat((8,), ('x',))
            table = dist.create_sharded(mesh, 'x', 1024, window=16)
            n = 8 * 64
            keys = jnp.arange(1, n + 1, dtype=jnp.uint32)
            vals = keys * 5
            spec = jax.tree.map(lambda _: P('x'), table)
            def ins(t, k, v):
                tl = dist._local(t)
                tl, st = dist.insert_independent(tl, k, v)
                return dist._relift(tl), st
            f = jax.jit(shard_map_compat(ins, mesh,
                                         in_specs=(spec, P('x'), P('x')),
                                         out_specs=(spec, P('x'))))
            table, st = f(table, keys, vals)
            def ret(t, k):
                return dist.retrieve_independent(dist._local(t), k, 'x')
            g = jax.jit(shard_map_compat(ret, mesh, in_specs=(spec, P('x')),
                                         out_specs=(P('x'), P('x'))))
            got, found = g(table, keys)
            assert np.asarray(found).all()
            assert (np.asarray(got) == np.asarray(vals)).all()
            print('OK')
        """)
        assert "OK" in out

    def test_erase_distributed(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core import distributed as dist
            from repro.core.compat import make_mesh_compat, shard_map_compat
            mesh = make_mesh_compat((8,), ('x',))
            table = dist.create_sharded(mesh, 'x', 1024, window=16)
            n = 8 * 128
            keys = jnp.arange(1, n + 1, dtype=jnp.uint32)
            table, _, ov = jax.jit(dist.shard_insert, static_argnums=(0, 1))(
                mesh, 'x', table, keys, keys)
            assert int(np.asarray(ov).sum()) == 0
            spec = jax.tree.map(lambda _: P('x'), table)
            def er(t, k):
                tl, erased, ov = dist.erase_distributed(dist._local(t), k, 'x')
                return dist._relift(tl), erased, ov[None]
            f = jax.jit(shard_map_compat(er, mesh, in_specs=(spec, P('x')),
                                         out_specs=(spec, P('x'), P('x'))))
            half = keys[:n // 2]
            pad = jnp.concatenate([half, jnp.arange(
                2 * n, 2 * n + n // 2, dtype=jnp.uint32)])
            table, erased, ov = f(table, pad)
            assert int(np.asarray(ov).sum()) == 0
            assert np.asarray(erased)[:n // 2].all()
            got, found, _ = jax.jit(dist.shard_retrieve,
                                    static_argnums=(0, 1))(
                mesh, 'x', table, keys)
            found = np.asarray(found)
            assert not found[:n // 2].any() and found[n // 2:].all()
            print('OK')
        """)
        assert "OK" in out


class TestGradSyncCompression:
    def test_int8_cross_pod_sync(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed import collectives
            from repro.training import compression as comp
            from repro.core.compat import make_mesh_compat, set_mesh_compat
            mesh = make_mesh_compat((2, 4), ('pod', 'data'))
            sync = collectives.make_grad_sync(
                mesh, comp.CompressionConfig(kind='int8'))
            g = {'w': jnp.asarray(np.random.default_rng(0).normal(
                size=(64, 64)).astype(np.float32))}
            with set_mesh_compat(mesh):
                out = jax.jit(sync)(g)
            np.testing.assert_allclose(np.asarray(out['w']),
                                       np.asarray(g['w']), atol=0.05)
            print('OK')
        """)
        assert "OK" in out

    def test_none_sync_is_mean(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed import collectives
            from repro.training import compression as comp
            from repro.core.compat import make_mesh_compat, set_mesh_compat
            mesh = make_mesh_compat((2, 4), ('pod', 'data'))
            sync = collectives.make_grad_sync(
                mesh, comp.CompressionConfig(kind='none'))
            g = {'w': jnp.ones((8, 8), jnp.float32)}
            with set_mesh_compat(mesh):
                out = jax.jit(sync)(g)
            np.testing.assert_allclose(np.asarray(out['w']), 1.0)
            print('OK')
        """)
        assert "OK" in out


class TestElastic:
    def test_kill_and_resume_on_smaller_mesh(self):
        out = _run("""
            import repro.launch.elastic as el
            import sys
            sys.exit(el.main(['--steps', '16', '--kill-at', '8']))
        """)
        assert "elastic restart OK" in out


class TestPipelineParallel:
    def test_pipelined_forward_matches_sequential(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.distributed import pipeline_parallel as pp
            from repro.core.compat import make_mesh_compat, shard_map_compat
            mesh = make_mesh_compat((4,), ('pod',))
            L, D, M, mb = 8, 16, 8, 4
            key = jax.random.PRNGKey(0)
            blocks = {'w': jax.random.normal(key, (L, D, D)) * 0.1}
            x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, D))
            def block_fn(blk, h):
                return jnp.tanh(h @ blk['w'])
            # sequential reference
            ref = x
            for i in range(L):
                ref = block_fn({'w': blocks['w'][i]}, ref)
            staged = pp.stage_params(blocks, 4)
            spec = jax.tree.map(lambda _: P('pod'), staged)
            f = jax.jit(shard_map_compat(
                lambda s, xx: pp.pipelined_apply(block_fn, s, xx, 'pod'),
                mesh, in_specs=(spec, P()), out_specs=P()))
            out = f(staged, x)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)
            print('OK')
        """, devices=4)
        assert "OK" in out
