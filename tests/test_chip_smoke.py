"""chip_smoke.py: the CPU rehearsal runs every phase, and without its
explicit flag nothing but a TPU gets a result."""

import json
import os
import subprocess
import sys


def _run(repo_root, tmp_path, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    # conftest's off switch is for the in-process suite; the smoke is its
    # own process and the caches are part of what it proves
    env.pop("NERRF_NO_COMPILE_CACHE", None)
    return subprocess.run(
        [sys.executable, str(repo_root / "chip_smoke.py"), *argv],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)


def test_rehearsal_runs_every_phase(repo_root, tmp_path):
    """The same code the chip runs — barrier, the two-route ops against the
    host, both train buckets through train.run, serve-detect on the
    checkpoint, and (conftest's 8 virtual devices) the dp x tp leg — at a
    toy width, with both compile caches placed by the environment."""
    before = set(os.listdir(repo_root))
    r = _run(repo_root, tmp_path, "--rehearsal")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    lines = r.stdout.strip().splitlines()
    assert all("rehearsal" in line for line in lines), (
        "every line a rehearsal prints must say it is one")
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    for phase in ("barrier", "kernels", "train@256n/512e",
                  "train@512n/1024e", "serve", "four"):
        assert f"phase {phase}: ok" in r.stdout, phase
    # both caches under the directory the environment named, none elsewhere
    assert any((tmp_path / "cc" / "aot").iterdir())
    assert set(os.listdir(repo_root)) == before


def test_without_a_tpu_there_is_no_result(repo_root, tmp_path):
    r = _run(repo_root, tmp_path)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    assert r.stderr.strip().splitlines()[-1].startswith("chip_smoke: no TPU")
