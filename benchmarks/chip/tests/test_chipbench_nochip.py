"""The benchmark never falls back to the CPU."""
import os
import subprocess
import sys

from chipbench import manifest


def test_exits_nonzero_naming_the_platform_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = manifest.load()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(manifest.HERE / "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 77), "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "'cpu'" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{"), line
