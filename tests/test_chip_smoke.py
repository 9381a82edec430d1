"""The chip smoke's phases, rehearsed on the CPU at a tiny size.

``chip_smoke.py`` refuses to run without a TPU, so these tests drive its
phase functions directly: the same load, waves, writes under load and
reference comparison, on host-device tables and the kernels' jnp twins.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core import generate  # noqa: E402


def test_smoke_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def test_reference_replays_generated_versions():
    graph = generate(chip_smoke.b1_spec(3, 80, 40))
    ref = chip_smoke.Reference(chip_smoke.generated_commits(graph))
    keys = graph.store.keys()
    for v in graph.versions:
        want = {int(keys[r]): graph.store.payload(int(r))
                for r in graph.members(v)}
        assert ref.state(v) == want


def test_reference_catches_a_wrong_answer():
    graph = generate(chip_smoke.b1_spec(4, 50, 10))
    ref = chip_smoke.Reference(chip_smoke.generated_commits(graph))
    from repro.core import Q

    class Liar:
        def serve(self, queries):
            return [type("R", (), {"value": None})() for _ in queries]

    with pytest.raises(AssertionError, match="differs from the reference"):
        chip_smoke.serve_checked(Liar(), ref, [Q.version(5)])


@pytest.mark.parametrize("seed", [0, 1])
def test_one_chip_phases_match_reference(seed):
    stored = chip_smoke.one_chip("cpu", seed, base_records=60, n_versions=40,
                                 n_waves=2, wave_size=32, n_commits=2)
    assert stored > 60 * chip_smoke.RECORD_BYTES


def test_four_chip_phase_on_four_host_devices():
    """The --chips 4 placement and waves, on four virtual CPU devices in a
    child process (the device count is fixed when JAX starts)."""
    code = ("import json, jax, chip_smoke; print(json.dumps(chip_smoke."
            "four_chips(jax.devices(), 0, 60, 30, 2, 32)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert any("span 4 chips" in line for line in lines)
    assert json.loads(lines[-1]) > 0


def test_make_wave_mixes_every_query_kind():
    graph = generate(chip_smoke.b1_spec(5, 50, 12))
    ref = chip_smoke.Reference(chip_smoke.generated_commits(graph))
    wave = chip_smoke.make_wave(np.random.default_rng(0), ref,
                                ref.versions, 64)
    kinds = {q.kind for q in wave}
    assert kinds == {"version", "record", "records", "range", "evolution",
                     "where", "and", "count"}
