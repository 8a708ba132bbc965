"""One tiny cell end to end in the CPU rehearsal mode, added to a copy of
the benchmark from data files alone; and the refusals of the entry."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

from perfbench import harness
from perfbench.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_tiny_cell_added_by_data_files_runs_end_to_end(tmp_path, capsys):
    root = tiny.make_copy(tmp_path)
    res = harness.run(root, "tiny.rag", 2**31 + 99, 3.0, False,
                      time.perf_counter(), require_tpu=False)
    err = capsys.readouterr().err
    # the warm-up slots' feedback plus the window's crosses the router's
    # update threshold inside the window, with no compile there
    assert int(re.search(r"PPO updates (\d+)", err).group(1)) >= 1
    assert re.search(r"compiles in window 0\b", err), err
    line = json.dumps(res)
    back = json.loads(line)
    assert KEYS <= set(back)
    assert list(back)[-1] == "checked"          # compared numbers last
    assert back["correct"] is True
    assert back["attempted"] >= 2 and back["failed"] == 0
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert set(back["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
        if "tiny.rag" in m.get("workloads", ["tiny.rag"])}
    for m in back["metrics"].values():
        assert m["value"] > 0
    assert back["device"]["platform"] == "cpu"
    for name, c in back["checked"].items():
        assert c["limit"] is not None, name


def _run_entry(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "edge2.rag-steady", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_entry_refuses_a_backend_other_than_the_tpu():
    p = _run_entry(tiny.ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "tpu" in p.stderr
    assert p.stdout.strip() == ""


def test_entry_fails_without_the_program(tmp_path):
    shutil.copytree(tiny.ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    p = _run_entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
