"""The benchmark's command end to end on the CPU: each cell runs for about a
second in the rehearsal, whose line never reads as a device result;
without the rehearsal switch, or without the program beside it, the command
fails and prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN = os.path.join(HERE, "run.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def bench(args, cwd=ROOT, timeout=300):
    return subprocess.run([sys.executable, RUN, *args], cwd=cwd, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("workload", ["n1.readout_closed", "n1000.readout_closed"])
def test_each_cell_rehearses(workload):
    p = bench(["--workload", workload, "--seed", "4294967311", "--seconds", "1",
               "--trace", "1", "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["rehearsal"] is True and line["correct"] is False
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["outputs_match"] is True, line["checks"]
    assert line["attempted"] > 0
    assert "compile_s" in line["rehearsal_metrics"]
    assert p.stderr.rstrip().splitlines()[-1].startswith("check ")


def test_without_a_chip_no_result():
    p = bench(["--workload", "n1.readout_closed", "--seed", "1", "--seconds", "1",
               "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "n1.readout_closed", "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--rehearse"], cwd=tmp_path,
                       env=dict(ENV, PYTHONPATH=""), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("group,key,value", [
    ("plan", "learn", "rls"), ("plan", "autoscale", {"min_slots": 8}),
    ("spec", "tableau", "euler"), ("spec", "dtype", "float64"),
    ("plan", "sharding", "lanes"),
])
def test_configuration_the_harness_does_not_run_is_refused(group, key, value):
    sys.path.insert(0, HERE)
    from benchlib import registry, system

    cfg = registry.Cell("n1.readout_closed").config
    assert system.validate(cfg) is cfg
    cfg[group][key] = value
    with pytest.raises(ValueError, match=f"{group}.{key}"):
        system.validate(cfg)
