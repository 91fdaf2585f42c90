"""Stream planner on the pod: plan pipeline-parallel training for an
assigned architecture, showing the latency/memory scheduling trade-off that
the paper demonstrates on edge SoCs (Fig. 7) reappearing at datacenter scale
— then run the planned pipeline for real on host devices.

  PYTHONPATH=src python examples/plan_pipeline.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
from jax.sharding import AxisType
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, reduce_config
from repro.core.planner import evaluate_pipeline
from repro.models.module import init_from_specs
from repro.models.zoo import build_param_specs
from repro.train.pipeline import make_pipeline_loss

cfg_full = ARCHS["deepseek-67b"]
shape = SHAPES["train_4k"]
print(f"planning {cfg_full.name} x {shape.name} on 256 chips")
for prio in ("latency", "memory"):
    for ns, nm in ((4, 8), (4, 32), (8, 32)):
        p = evaluate_pipeline(cfg_full, shape, n_stages=ns,
                              chips_per_stage=256 // ns, n_microbatches=nm,
                              priority=prio)
        print(f"  {prio:8s} stages={ns} micro={nm:2d}: "
              f"step={p.est_step_s:7.2f}s peak={p.est_peak_bytes / 2**30:6.1f}GB "
              f"util={p.schedule.utilization().mean():.2f}")

print("\nexecuting a 2-stage pipeline on host devices (reduced config):")
cfg = reduce_config(ARCHS["llama3.2-3b"], n_layers=4)
mesh = jax.make_mesh((2, 2), ("pipe", "data"),
                     axis_types=(AxisType.Auto,) * 2)
params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0))
params["layers"] = jax.tree.map(lambda a: a.reshape((2, 2) + a.shape[1:]),
                                params["layers"])
key = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab),
         "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab)}
loss_fn = make_pipeline_loss(cfg, mesh, n_stages=2, n_microbatches=2)
with jax.set_mesh(mesh):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
print(f"pipeline loss={float(loss):.4f}; grads flow through ppermute: "
      f"{all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))}")
