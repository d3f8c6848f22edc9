"""The four-chip city is city130_t8's deployment over a fleet: the same
recording, features, search and check, with its worker count the shard
count the harness gives it (the cell's ``chips``)."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts the benchmark on the path)

from harness import drive  # noqa: E402

FLEET = "city130_t8_fleet4"
# the keys that define the deployment, as opposed to its name and prose
DEPLOYMENT = ("network", "stream", "profile", "features", "serve", "queries",
              "check")


@pytest.mark.parametrize("key", DEPLOYMENT)
def test_fleet_config_serves_city130_t8(key):
    assert drive.load_config(FLEET)[key] == drive.load_config("city130_t8")[key]


def test_fleet_workers_are_its_cells_chips():
    bench = drive.load_benchmark()
    cfg = drive.load_config(FLEET)
    (entry,) = [c for c in bench["configs"] if c["name"] == FLEET]
    assert entry["file"] == f"bench/configs/{FLEET}.json"
    assert entry["reduced"] == list(cfg["reduced"]) == []
    cells = [w for w in bench["workloads"] if w["config"] == FLEET]
    assert cells and all(w["chips"] == cfg["workers"] for w in cells)
