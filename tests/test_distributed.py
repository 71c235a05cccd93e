"""Multi-device semantics (8 host devices, isolated subprocesses):

* sharded-mesh train step equals the single-device step;
* compressed cross-pod gradient exchange (the paper technique) learns and
  tracks the uncompressed baseline (error feedback);
* elastic re-mesh: checkpoint on mesh (4,2) restores and continues on (2,4)
  bit-compatibly with an uninterrupted run.
"""
import os
import subprocess
import sys

import pytest

_MAIN = os.path.join(os.path.dirname(__file__), "_distributed_main.py")


def _run(scenario, timeout=560):
    r = subprocess.run([sys.executable, _MAIN, scenario],
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"{scenario}:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert f"OK {scenario}" in r.stdout


@pytest.mark.slow
def test_dist_equivalence():
    _run("dist_equivalence")


@pytest.mark.slow
def test_compressed_grads():
    _run("compressed_grads")


@pytest.mark.slow
def test_remesh():
    _run("remesh")


def test_flash_heads_per_shard():
    _run("flash_heads")
