"""A HAIL store placed over four chips (four virtual CPU devices, in the
subprocess ``placed_worker.py``): each replica's blocks live on the chip of
their datanode, the placed upload is bit-equal to the one-device upload,
each replica's host key ranges equal a numpy oracle, HailServer's answers
equal the oracle and the one-device store's, every reader program stays on
its split's chip, and one chip is today's store."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

WORKER = pathlib.Path(__file__).with_name("placed_worker.py")
CHECKS = ["placement", "bit_equal_upload", "key_ranges", "answers",
          "reads_stay_on_their_chip", "failover_reads_other_chips",
          "one_chip_is_todays_store",
          "placed_store_refuses_rewrites_and_cross_chip_reads"]


@pytest.fixture(scope="module")
def results():
    root = WORKER.parents[1]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(root / "src")] + [p for p in [os.environ.get("PYTHONPATH")]
                               if p]))
    r = subprocess.run([sys.executable, str(WORKER)], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return {rec["check"]: rec for rec in map(json.loads, (
        line for line in r.stdout.splitlines() if line.startswith("{")))}


@pytest.mark.parametrize("name", CHECKS)
def test_placed_store(results, name):
    assert name in results, sorted(results)
    assert results[name]["ok"], results[name].get("error")
