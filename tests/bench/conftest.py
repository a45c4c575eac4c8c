"""Fixtures of the benchmark's own tests: the repository root on the path,
and each cell cut to a size the CPU runs in seconds."""
from __future__ import annotations

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink(cell):
    """The cell at a CPU size on one device: same model family, job and
    limits; small images, nodes, batches and widths (the program's
    ``reduced`` mamba2); a sharded job runs as one hybrid block."""
    c, t = dict(cell.config), dict(cell.traffic)
    if "widths" in c:
        c.update(image_hw=8, data=dict(c["data"], hw=8, n_data=1024,
                                       train_frac=0.5))
        t.update(nodes=4, batch=4, min_per_client=12)
    else:
        c.update(d_model=128, n_layer=2, d_state=16, head_dim=16,
                 vocab_size=512, vocab_rows=512,
                 model={"name": "transformer",
                        "kwargs": {"arch": "mamba2-130m", "reduced": True}},
                 data={"dataset": "lm_domains", "vocab": 256})
        t.update(batch=2, seq_len=128, n_seq_per_domain=64,
                 min_per_client=6)
    if t["runtime"] == "sharded":       # one device: the nodes as one block
        t["runtime"] = "hybrid"
    t.update(block_steps=2, trace_settle_steps=1, trace_steps=2)
    c["params_per_node"] = cell.model.param_count(c)
    return dataclasses.replace(cell, config=c, traffic=t, chips=1)


# mamba2-130m's job on four chips, ready for a cell of its own; its tests
# hold it to the calibrated ResNet cell's limits
MAMBA = ("mamba2_130m_ring4_sharded", "mamba2_130m", "ring4_sharded_4x512", 4)
LIMITS_OF = "resnet20_ring16_dir0.1_hybrid"


def cell_named(name):
    import json
    from bench import harness
    if name == MAMBA[0]:
        with open(harness.ROOT / "BENCHMARK.json") as f:
            end_to_end = json.load(f)["end_to_end"]
        with open(harness.ROOT / "bench" / "limits" / f"{LIMITS_OF}.json") as f:
            limits = json.load(f)
        return harness.make_cell(*MAMBA, limits=limits,
                                 end_to_end=end_to_end)
    return harness.load_cell(name)


@pytest.fixture
def cell():
    return cell_named


@pytest.fixture
def tiny_cell():
    def make(name):
        return shrink(cell_named(name))

    return make
