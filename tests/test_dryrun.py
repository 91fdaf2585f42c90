"""Integration test of the dry-run path (lower + compile + roofline) on a
small host mesh — exercises exactly what launch/dryrun.py does per cell,
without the 512-device production setting."""
import jax
from jax.sharding import AxisType
import pytest

from repro.launch.dryrun import lower_cell


def _mesh(shape=(2, 4)):
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_lower_cell_train_reports_roofline():
    compiled, rep = lower_cell("llama3.2-3b", "train_4k", multi_pod=False,
                               mesh=_mesh())
    assert not rep.get("skipped") and not rep.get("failed")
    r = rep["roofline"]
    assert r["t_compute_s"] > 0 and r["t_memory_s"] > 0
    assert r["bottleneck"] in ("compute", "memory", "collective")
    assert 0 < r["useful_flops_ratio"] < 2.0
    assert r["collective_bytes"] > 0  # sharded step must communicate
    del compiled


def test_lower_cell_decode_and_skip():
    compiled, rep = lower_cell("llama3.2-3b", "decode_32k", multi_pod=False,
                               mesh=_mesh())
    assert rep["kind"] == "decode" and not rep.get("failed")
    del compiled
    # full-attention arch skips long_500k with a documented reason
    _, rep2 = lower_cell("llama3.2-3b", "long_500k", multi_pod=False,
                         mesh=_mesh())
    assert rep2["skipped"] and "sub-quadratic" in rep2["why"]
