"""Traffic mixes: the training job a cell runs.

A traffic mix is a JSON file under ``bench/traffic/``: the job's
parameters (sync mode, staleness, global batch, logical shards, steps per
superstep, workers, kernel path).  This module reads and checks it.  What
the job trains on comes from the configuration's family module
(``bench/families/<family>.py``), made from the run's seed.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

#: keys every traffic file states
KEYS = ("sync", "staleness", "batch", "logical_shards", "superstep",
        "workers", "use_kernel")


def load(name: str) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    missing = [k for k in KEYS if k not in spec]
    if missing:
        raise ValueError(f"traffic {name!r} lacks {missing}")
    if spec["batch"] % spec["logical_shards"]:
        raise ValueError(f"traffic {name!r}: logical_shards must divide "
                         f"the batch")
    if spec["logical_shards"] % spec["workers"]:
        raise ValueError(f"traffic {name!r}: workers must divide "
                         f"logical_shards")
    return spec
