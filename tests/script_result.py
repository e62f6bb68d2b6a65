"""Run a test script in a subprocess and return its RESULT line — once
per test run.

The engine tests run their multi-device checks as ``python -c SCRIPT``
(the fake device count must be set before jax starts) and print one
``RESULT <json>`` line. Under pytest-xdist with ``--dist load`` a
module-scoped fixture runs in every worker that is given one of the
module's tests, so a script of several minutes would run several times
at once. Here the first worker to ask runs it; the others wait on a file
lock and read its output. The key is the xdist run id and the script
text, so nothing is shared between runs.
"""
import fcntl
import hashlib
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, timeout):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    try:
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return -1, "", f"script timed out after {timeout}s"
    return proc.returncode, proc.stdout, proc.stderr


def run_result(script: str, timeout: int = 1800) -> dict:
    run_id = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    if run_id is None:
        rc, out, err = _run(script, timeout)
    else:
        cache = os.path.join(tempfile.gettempdir(),
                             f"repro-test-scripts-{run_id}")
        os.makedirs(cache, exist_ok=True)
        path = os.path.join(cache,
                            hashlib.sha256(script.encode()).hexdigest())
        with open(path + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if os.path.exists(path + ".json"):
                with open(path + ".json") as f:
                    rc, out, err = json.load(f)
            else:
                rc, out, err = _run(script, timeout)
                with open(path + ".json", "w") as f:
                    json.dump([rc, out, err], f)
    assert rc == 0, err[-3000:]
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])
