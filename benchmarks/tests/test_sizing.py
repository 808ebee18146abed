"""The runner's open-file step.  It raises the soft limit to the hard one,
which the engine inherits, and requires only what the runner and its
generators hold: so each accepted cell is sized as before, and a node with
more groups than descriptors is no longer refused before the engine starts."""
import importlib
import json
import os
import resource

import pytest

from lib import engine
from test_rehearsal import MANIFEST, ROOT

HERE = os.path.join(ROOT, "benchmarks")
CELLS = MANIFEST["workloads"]
CHIP_HOST_HARD = 20000          # RLIMIT_NOFILE on the TPU v5e hosts


@pytest.fixture(scope="module")
def run():
    return importlib.import_module("run")


def files_of(cell):
    def load(kind, name):
        with open(os.path.join(HERE, kind, name + ".json")) as f:
            return json.load(f)
    return load("configs", cell["config"]), load("traffic", cell["traffic"])


@pytest.fixture
def nofile(monkeypatch):
    """RLIMIT_NOFILE as a dict the runner's calls read and write."""
    limits = {"soft": 1024, "hard": CHIP_HOST_HARD}

    def get(which):
        assert which == resource.RLIMIT_NOFILE
        return limits["soft"], limits["hard"]

    def set_(which, pair):
        assert which == resource.RLIMIT_NOFILE
        assert pair[1] == limits["hard"]
        limits["soft"] = pair[0]

    monkeypatch.setattr(resource, "getrlimit", get)
    monkeypatch.setattr(resource, "setrlimit", set_)
    return limits


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_an_accepted_cell_is_sized_as_before(run, nofile, cell):
    config, traffic = files_of(cell)
    assert run.raise_nofile(traffic["clients"]) == CHIP_HOST_HARD
    assert nofile["soft"] == nofile["hard"]
    # What the step used to require also fits: the outcome is the same.
    assert config["groups"] + traffic["clients"] + run.NOFILE_SPARE \
        <= CHIP_HOST_HARD
    assert engine.BOOT_DEADLINE_S == 420.0
    assert run.LIST_PHASE_S == 300.0


def test_a_100000_group_node_passes_the_descriptor_step(run, nofile):
    config, traffic = files_of(
        next(c for c in CELLS if c["name"] == "ycsb-a-10kgroups"))
    groups = 100000
    assert groups + traffic["clients"] + run.NOFILE_SPARE > CHIP_HOST_HARD
    assert run.raise_nofile(traffic["clients"]) == CHIP_HOST_HARD
    assert nofile["soft"] == nofile["hard"]


def test_a_hard_limit_under_the_clients_and_the_spare_refuses(run, nofile):
    nofile["hard"] = 1000 + run.NOFILE_SPARE - 1
    with pytest.raises(run.RunFailure, match=(
            rf"open-file hard limit {nofile['hard']} < "
            rf"{1000 + run.NOFILE_SPARE} needed \(1000 clients \+ "
            rf"{run.NOFILE_SPARE} spare\)")):
        run.raise_nofile(1000)
    assert nofile["soft"] == 1024


@pytest.mark.parametrize("soft", [1024, 10**6])
def test_an_unlimited_hard_limit_keeps_the_larger_soft_one(run, nofile, soft):
    nofile["hard"] = resource.RLIM_INFINITY
    nofile["soft"] = soft
    need = 1000 + run.NOFILE_SPARE
    assert run.raise_nofile(1000) == max(soft, need)
    assert nofile["soft"] == max(soft, need)
