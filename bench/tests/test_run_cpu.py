"""``bench/run.py`` refuses to run without an accelerator, and the
benchmark's files keep to the contract's shape."""
import json
import os
import re
import subprocess
import sys

from bench import harness

ROOT = harness.ROOT


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "resnet50-224.w8a8-backlog", "--seed", str(2**31 + 7), "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_cell_finds_its_files():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    names = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    metrics = spec["end_to_end"] + spec["per_layer"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in metrics:
        assert names.match(m["name"])
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert (ROOT / "bench/drivers" / f"{cell.config['driver']}.py").exists()
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        assert all(m["moves"] in reported for m in cell.per_layer)
        assert all(len(v) <= 200 for v in (w["why"],))
    assert len(json.dumps(spec)) < 64 * 1024
