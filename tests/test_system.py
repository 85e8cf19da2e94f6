"""End-to-end behaviour tests for the paper's system.

The paper's headline behaviours, validated at CPU scale:
  1. bulk build + query at high load factor (the Fig-5 scenario),
  2. multi-value robustness across key multiplicities (Fig-7),
  3. bucket-list storage density beating pure OA at high multiplicity,
  4. the metagenomics pipeline (Fig-8): minhash -> bucket-list -> classify,
  5. the end-to-end LM training driver (launch.train) and serving driver.
"""

import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket_list as bl
from repro.core import multi_value as mv
from repro.core import single_value as sv


def test_bulk_build_query_at_097_density():
    """Paper §V-A: WarpCore stays functional at rho = 0.97 where competing
    schemes degrade/fail; scalar-LP baseline needs far longer probe chains."""
    t = sv.create(4096, window=32)
    n = int(t.capacity * 0.97)
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.choice(np.arange(1, 10 * n, dtype=np.uint32),
                                  size=n, replace=False))
    vals = keys ^ jnp.uint32(0x5A5A)
    t, st = sv.insert(t, keys, vals)
    assert (np.asarray(st) == 0).all()
    got, found = sv.retrieve(t, keys)
    assert found.all() and (got == vals).all()


def test_multi_value_flat_throughput_structure():
    """Fig 7 structure: total probe work per retrieved value stays bounded
    as multiplicity grows (COPS retrieves multiple values per window)."""
    for r in (1, 8, 32):
        t = mv.create(8192, window=32)
        n_keys = 2048 // r
        keys = jnp.asarray(np.repeat(np.arange(1, n_keys + 1,
                                               dtype=np.uint32), r))
        t, st = mv.insert(t, keys, jnp.arange(len(keys), dtype=jnp.uint32))
        assert (np.asarray(st) == 0).all()
        q = jnp.arange(1, n_keys + 1, dtype=jnp.uint32)
        cnt = np.asarray(mv.count_values(t, q))
        assert (cnt == r).all()


def test_bucket_list_denser_than_oa_at_high_multiplicity():
    """§IV-C: for r >> 1 the bucket list stores each key once, the OA table
    r times — bucket list wins on stored-pairs per allocated slot."""
    r, n_keys = 32, 64
    keys = jnp.asarray(np.repeat(np.arange(1, n_keys + 1, dtype=np.uint32), r))
    vals = jnp.arange(len(keys), dtype=jnp.uint32)

    oa = mv.create(4096, window=32)
    oa, _ = mv.insert(oa, keys, vals)
    oa_slots = oa.capacity * 2                       # key+value words
    oa_useful = int(oa.count)                        # pairs stored

    t = bl.create(128, pool_capacity=n_keys * r + 200, s0=r, growth=1.0)
    t, _ = bl.insert(t, keys, vals)
    bl_slots = t.key_store.capacity * 3 + t.pool_capacity
    bl_useful = int(sum(np.asarray(bl.count_values(
        t, jnp.arange(1, n_keys + 1, dtype=jnp.uint32)))))

    assert bl_useful == oa_useful == n_keys * r
    density_oa = oa_useful * 2 / oa_slots
    density_bl = (bl_useful + n_keys) / bl_slots
    assert density_bl > density_oa


def test_metagenomics_pipeline_classifies():
    """Mini Fig-8: build a reference DB from synthetic genomes, classify
    reads back to their source genome via minhash + bucket list."""
    from repro.kernels.minhash import ops as mh
    rng = np.random.default_rng(42)
    k, s = 16, 24
    genomes = [rng.integers(0, 4, 2000).astype(np.uint8) for _ in range(4)]

    table = bl.create(8192, pool_capacity=1 << 14, s0=2, growth=1.5)
    for gid, g in enumerate(genomes):
        sk = np.asarray(mh.sketch_reads(jnp.asarray(g[None]), k=k, s=256))
        hashes = sk[0][sk[0] != 0xFFFFFFFF]
        hashes = np.minimum(hashes, 0xFFFFFFFD)
        table, st = bl.insert(table, jnp.asarray(hashes),
                              jnp.full((len(hashes),), gid, jnp.uint32))
        assert (np.asarray(st) == 0).all()

    correct = 0
    n_reads = 12
    for _ in range(n_reads):
        gid = int(rng.integers(0, 4))
        start = int(rng.integers(0, 1500))
        read = genomes[gid][start:start + 400]
        sk = np.asarray(mh.sketch_reads(jnp.asarray(read[None]), k=k, s=s))
        q = sk[0][sk[0] != 0xFFFFFFFF]
        q = np.minimum(q, 0xFFFFFFFD)
        out, off, cnt = bl.retrieve_all(table, jnp.asarray(q),
                                        out_capacity=len(q) * 8)
        votes = np.bincount(np.asarray(out)[:int(off[-1])], minlength=4)
        if votes.argmax() == gid:
            correct += 1
    assert correct >= n_reads * 0.75, f"classified {correct}/{n_reads}"


def test_train_driver_end_to_end(tmp_path):
    """launch.train: real CLI run with checkpointing + resume."""
    env = {**os.environ, "PYTHONPATH": "src"}
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "smollm-360m", "--smoke", "--batch", "4", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
            "--log-every", "5", "--lr", "3e-3"]
    # two runs, each well inside the test's time limit (root conftest.py)
    r = subprocess.run(base + ["--steps", "10"], capture_output=True,
                       text=True, timeout=80, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step 9" in r.stdout
    r2 = subprocess.run(base + ["--steps", "14", "--resume"],
                        capture_output=True, text=True, timeout=80, env=env)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 10" in r2.stdout


def test_serve_driver_end_to_end():
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-m", "repro.launch.serve",
                        "--arch", "smollm-360m", "--smoke", "--batch", "2",
                        "--prompt-len", "8", "--max-new", "8"],
                       capture_output=True, text=True, timeout=100, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tok/s" in r.stdout
