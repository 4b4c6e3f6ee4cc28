"""Root-script unit tests — pure host logic, no device."""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
import bench  # noqa: E402


def test_median_of_windows_extends_on_spread():
    import bench

    # stable series: exactly k windows run
    calls = []

    def stable(i):
        calls.append(i)
        return 100.0 + (i % 2)   # spread 1% << 20%
    med, vals, spread = bench._median_of_windows(stable, k=5)
    assert len(vals) == 5 and calls == [0, 1, 2, 3, 4]
    assert spread < 0.2 and 100.0 <= med <= 101.0

    # noisy series: keeps adding windows to max_k
    seq = iter([100.0, 200.0, 100.0, 200.0, 100.0, 200.0, 100.0, 200.0,
                100.0])

    def noisy(i):
        return next(seq)
    med2, vals2, spread2 = bench._median_of_windows(noisy, k=5, max_k=9)
    assert len(vals2) == 9          # capped, never infinite
    assert spread2 > 0.2            # honestly recorded even at the cap
    assert med2 in (100.0, 150.0, 200.0)


def test_chip_smoke_refuses_the_cpu_before_building_anything(tmp_path):
    """Off the chip every kernel picks its interpreter and every caller
    its dense reference, so a smoke run that landed on the CPU would pass
    on the reference: it must exit non-zero, print NO result on stdout,
    and say what it found — before any phase starts."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""       # no result, no phase output
    verdict = json.loads(out.stderr.strip().splitlines()[-1])
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
