"""The command refuses to measure where it cannot: without a TPU, and in a
checkout that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from conftest import REPO


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet256-feasible",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_no_tpu_exits_nonzero_without_result():
    proc = _run(REPO, {"PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    _no_result(proc)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert proc.returncode != 0
    _no_result(proc)


class _Cell:
    """Calls of 20 ms that finish in the order they were sent."""

    def __init__(self, ahead):
        self.ahead, self.sent, self.log = ahead, 0, []

    def dispatch(self):
        self.sent += 1
        self.log.append(("send", self.sent))
        return self.sent

    def wait(self, n):
        import time
        time.sleep(0.02)
        self.log.append(("wait", n))
        return n


def test_window_keeps_calls_ahead_and_waits_for_all_it_sent():
    import conftest  # noqa: F401  (puts the repository on sys.path)
    from bench import run
    for ahead in (0, 1, 2):
        cell = _Cell(ahead)
        outs, elapsed = run.window(cell, 0.1)
        assert outs == list(range(1, cell.sent + 1))  # every call, in order
        assert elapsed >= 0.02 * cell.sent >= 0.1
        waits = [i for i, (what, _) in enumerate(cell.log) if what == "wait"]
        for k, i in enumerate(waits):
            in_flight = sum(what == "send" for what, _ in cell.log[:i]) - k
            assert in_flight <= ahead + 1
        # while time was left, each wait had ``ahead`` calls queued behind it
        assert cell.log[:ahead + 2] == \
            [("send", n) for n in range(1, ahead + 2)] + [("wait", 1)]
