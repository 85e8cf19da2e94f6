"""chip_smoke.py on the CPU: its phases at a tiny size pass their own checks,
a wrong answer fails them, and the script refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402


def _quiet(_line):
    pass


def test_insert_retrieve_phase_passes_its_checks():
    cs.phase_insert_retrieve(1 << 10, seed=5, report=_quiet)


def test_pallas_phase_is_bit_exact_with_jax():
    cs.phase_pallas(256, seed=5, rows=97, require_kernel=False, report=_quiet)


def test_distributed_phase_passes_its_checks():
    mesh = cs.make_mesh_compat((1,), (cs.AXIS,), devices=jax.devices()[:1])
    cs.phase_distributed(mesh, 512, 2, seed=5, report=_quiet)


def test_a_wrong_answer_fails_the_phase(monkeypatch):
    real = cs.sv.retrieve

    def off_by_one(table, keys, **kw):
        vals, found = real(table, keys, **kw)
        return vals.at[3].add(1), found

    monkeypatch.setattr(cs.sv, "retrieve", off_by_one)
    with pytest.raises(cs.CheckFailed, match="value wrong"):
        cs.phase_insert_retrieve(1 << 9, seed=6, report=_quiet)


def test_keys_are_distinct_and_avoid_the_sentinels():
    import jax.numpy as jnp
    keys = np.asarray(cs.keys_of(jnp.arange(1 << 16, dtype=jnp.uint32), 9))
    assert len(np.unique(keys)) == keys.size
    assert keys.max() < 0xFFFFFFFE


class _FakeChip:
    platform, device_kind = "tpu", "TPU (stand-in)"

    def memory_stats(self):
        return {}


@pytest.mark.parametrize("chips", [1, 4])
def test_main_runs_the_fixed_sizes_and_reports_the_chips_used(
        chips, monkeypatch, capsys):
    """main() drives the certified sizes (2^26 pairs, 2^20 Pallas keys;
    2^28 pairs in batches of 2^25 per chip on four) and its contract line
    counts the chips the run used, not the chips the host shows."""
    from repro.launch import compile_cache
    ran = []
    monkeypatch.setattr(jax, "devices", lambda: [_FakeChip()] * 4)
    monkeypatch.setattr(compile_cache, "configure", lambda: "unset")
    monkeypatch.setattr(cs, "make_mesh_compat", lambda *a, **k: "mesh")
    monkeypatch.setattr(cs, "phase_insert_retrieve",
                        lambda n, seed: ran.append(("insert_retrieve", n)))
    monkeypatch.setattr(cs, "phase_pallas",
                        lambda n, seed: ran.append(("pallas", n)))
    monkeypatch.setattr(cs, "phase_distributed", lambda mesh, n, batches,
                        seed: ran.append(("distributed", n, batches)))
    assert cs.main(["--chips", str(chips)]) == 0
    if chips == 1:
        assert ran == [("insert_retrieve", 1 << 26), ("pallas", 1 << 20)]
    else:
        assert ran == [("distributed", 1 << 27, 2)]
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU (stand-in)", "count": chips}}


def _run(script, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=100)


@pytest.mark.parametrize("alone", [False, True], ids=["in_repo", "alone"])
def test_script_fails_without_tpu_or_repo(alone, tmp_path):
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = _run(script, tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    last = (r.stdout.strip().splitlines() or ["{}"])[-1]
    assert not (last.startswith("{") and json.loads(last).get("ok"))
