"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and the
label digests it checks on the chip are the CPU's for the same seed."""
import os
import subprocess
import sys

import chip_smoke

ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_recorded_digests_match_a_cpu_run():
    """The PairSet-fed sessions are integer-exact, so the digests recorded
    for seed 0 must be what the fused round engine gives on the CPU."""
    results, pairs = chip_smoke.serve(0, fused=True)
    got = {name: chip_smoke.digest(results[name]) for name in pairs}
    assert got == chip_smoke.EXPECTED_DIGESTS[0]
