"""Run both halves of the main path once on a TPU and check what comes out.

  python chip_smoke.py             # one chip: explorer + serving phases
  python chip_smoke.py --chips 4   # four chips: sharded serving phase only

Explorer phase: a prefiltered design-space exploration of resnet18 on the
heterogeneous multi-core accelerator, with the batched fitness (the Pallas
wavefront kernel under the `serialize` contention model) compiled for the
chip. The chosen design must match the exact host scheduler, and the chip's
approximate scores must match the same program run on the host CPU.

Serving phase: the token engine serves rwkv6-3b at its published widths
with random weights through `repro.launch.serve.main`. Every request must
get all its tokens, and the first wave's prefill logits must match the same
program on the host CPU. `--chips 4` instead serves deepseek-moe-16b
sharded over a (1, 4) data x model mesh and shows where its bytes landed.

The script runs only on a TPU. Any failed check ends it with a non-zero
exit. The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# float32 scores: the chip and the host reduce in different orders, and the
# log-step kernel reassociates nothing the host reference does not, so two
# scores differ by at most the rounding of their longest accumulation, n
# terms -> n * 2**-24 relative; resnet18 at ("tile", 32, 1) has 601 CNs
# (3.6e-5), taken with margin
SCORE_RTOL = 1e-4
# bf16 logits: a bf16 program strays from its float32 evaluation by its own
# rounding noise, which the host measures for these weights and prompts as
# eps = max|host_bf16 - host_f32|. The chip rounds at other points and runs
# float32 contractions at the TPU's default (bf16-input) precision, so allow
# its own stray up to 2 * eps: |chip - host_bf16| <= 3 * eps.
LOGIT_EPS_FACTOR = 3.0


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def explorer_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api.session import ExplorationSession
    from repro.configs.paper_workloads import resnet18
    from repro.core.allocator import feasible_cores_per_layer
    from repro.core.vectorized import BatchedFitness, get_batched_fitness
    from repro.hw.catalog import mc_hetero
    from repro.kernels.ref import serialize_prefix_ref
    from repro.kernels.wavefront import serialize_prefix

    cpu = jax.devices("cpu")[0]

    # the kernel alone, against its reference on the host: the scans add
    # in the reference's order, so any difference is a lowering fault
    rng = np.random.default_rng(0)
    free0 = rng.uniform(0, 50, (256, 5)).astype(np.float32)
    rel = np.where(rng.uniform(size=(256, 5, 17)) < 0.3, -1e30,
                   rng.uniform(0, 100, (256, 5, 17))).astype(np.float32)
    dur = rng.uniform(0, 10, (256, 5, 17)).astype(np.float32)
    fin_c, free_c = jax.jit(serialize_prefix)(free0, rel, dur)
    with jax.default_device(cpu):
        fin_h, free_h = jax.jit(serialize_prefix_ref)(free0, rel, dur)
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))
                            / np.abs(np.asarray(b))))
              for a, b in ((fin_c, fin_h), (free_c, free_h)))
    log(f"  serialize_prefix chip vs host reference: max rel err {err!r}")
    check(err <= 1e-6, "wavefront kernel disagrees with its reference")

    w, acc, gran = resnet18(), mc_hetero(), ("tile", 32, 1)
    session = ExplorationSession(prefilter=True)
    result = session.explore(w, acc, granularity=gran, pop_size=24,
                             generations=8, seed=0)
    engine = session.engine(w, acc, gran)
    bf = get_batched_fitness(engine, priority="latency")
    log(f"  BatchedFitness: use_pallas={bf.use_pallas} "
        f"width={bf.width} cores={bf.n_cores}")
    check(bf.use_pallas, "the device path is not the Pallas kernel")
    hlo = bf._score_fn.lower(
        jnp.zeros((32, bf.n_layers), jnp.int32)).compile().as_text()
    check("tpu_custom_call" in hlo,
          "no tpu_custom_call in the compiled score program")
    log("  score program contains tpu_custom_call")
    ga = result.ga
    log(f"  GA: evaluations={ga.evaluations} "
        f"prefilter_screened={ga.prefilter_screened} "
        f"prefilter_pruned={ga.prefilter_pruned}")
    check(ga.prefilter_screened > 0, "the prefilter never screened")

    exact = engine.schedule(result.allocation, "latency")
    log(f"  explore: latency_cc={result.latency_cc!r} "
        f"energy_pj={result.energy_pj!r}")
    check((result.latency_cc, result.energy_pj)
          == (exact.latency_cc, exact.energy_pj),
          "explore's result differs from the exact host schedule")
    log("  explore result == engine.schedule(allocation) (exact oracle)")

    feas = feasible_cores_per_layer(w, acc)
    pop = np.stack([[f[rng.integers(len(f))] for f in feas]
                    for _ in range(64)])
    chip = bf.scores(pop)
    with jax.default_device(cpu):
        host = BatchedFitness(engine, use_pallas=False).scores(pop)
    rel_err = float(np.max(np.abs(chip - host) / np.abs(host)))
    log(f"  scores (64 genomes) chip vs host jnp path: max rel err "
        f"{rel_err!r} (tolerance {SCORE_RTOL})")
    check(bool(np.all(np.isfinite(chip))), "non-finite chip scores")
    check(rel_err <= SCORE_RTOL, "chip scores differ from the host's")


def _host_logits(cfg, params, prompts, max_len):
    """Last-token prefill logits of the same program on the host CPU: in
    bf16 as served, and evaluated in float32 on the same weights.

    The weights come to the host once, leaf by leaf, and each device leaf
    is deleted as soon as its copy lands: `params` is consumed. Keeping it
    would keep a cached host copy of every leaf too. For the float32 run
    only the unstacked leaves (embedding, head, final norm) are widened,
    which makes every activation float32; the program widens the stacked
    layer weights itself, exactly, so the host never holds a float32 copy
    beside them.

    XLA:CPU widens bf16 matmul operands to float32, and its loop-invariant
    code motion hoists that widening of every stacked layer weight out of
    the layer scan: float32 temporaries of twice the parameter bytes (66 GB
    for deepseek-moe-16b). With that pass off for these two programs the
    widening stays inside the loop, one layer at a time; the values are
    the same."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec

    from repro.models import zoo
    from repro.models.module import init_from_specs

    cpu = jax.devices("cpu")[0]
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=[cpu],
                         axis_types=(AxisType.Auto,) * 2)
    out = []
    with jax.default_device(cpu), jax.set_mesh(mesh):
        # onto the host mesh itself: an array put on the bare device still
        # names the serving mesh in its type, which the host trace refuses
        host_sharding = NamedSharding(mesh, PartitionSpec())

        def to_host(a):
            h = np.array(a)
            a.delete()
            return jax.device_put(h, host_sharding)

        host = jax.tree.map(to_host, params)
        for dtype in (jnp.bfloat16, jnp.float32):
            c = dataclasses.replace(cfg, dtype=dtype)
            p = jax.tree.map(
                lambda a: a.astype(dtype) if a.ndim <= 2 else a, host)
            caches = init_from_specs(
                zoo.build_cache_specs(c, prompts.shape[0], max_len),
                jax.random.PRNGKey(0))
            args = (p, {"tokens": jnp.asarray(prompts)}, caches)
            prefill = jax.jit(functools.partial(zoo.prefill, c, mesh=mesh))
            logits, _ = prefill.lower(*args).compile(compiler_options={
                "xla_disable_hlo_passes": "while-loop-invariant-code-motion",
            })(*args)
            out.append(np.asarray(logits, np.float32))
            del p, caches, args, logits
    return out


def serving_phase(arch: str, *, requests: int, slots: int, prompt_len: int,
                  max_new: int, model_parallel: int = 1) -> None:
    import jax
    import numpy as np

    from repro.configs import ARCHS
    from repro.launch import serve

    engine, reqs = serve.main([
        "--arch", arch, "--requests", str(requests),
        "--batch-slots", str(slots), "--prompt-len", str(prompt_len),
        "--max-new", str(max_new), "--model-parallel", str(model_parallel)])
    check(engine.cfg == ARCHS[arch],
          "serve.main did not serve the published config")
    counts = [len(r.out_tokens) for r in reqs]
    log(f"  {len(reqs)} requests served, tokens per request {counts}")
    check(all(c == max_new for c in counts),
          "a request did not get exactly max_new_tokens tokens")

    if model_parallel > 1:
        leaves = jax.tree.leaves(engine.params)
        total = sum(a.nbytes for a in leaves)
        split = sum(a.nbytes for a in leaves
                    if len({s.index for s in a.addressable_shards}) > 1)
        log(f"  parameters: {total} bytes, {split / total:.4f} of them in "
            f"leaves split across devices")
        in_use = []
        for d in jax.devices():
            st = d.memory_stats() or {}
            in_use.append(st.get("bytes_in_use", 0))
            log(f"  {d}: memory_stats bytes_in_use={st.get('bytes_in_use')} "
                f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
                f"bytes_limit={st.get('bytes_limit')}")
        check(min(in_use) > 0 and max(in_use) <= 1.25 * min(in_use),
              "parameters are not spread evenly over the devices")
        check(max(in_use) < total, "one device holds every parameter")

    wave = reqs[:slots]
    chip = np.asarray(engine.prefill_logits(wave), np.float32)
    first = [r.out_tokens[0] for r in wave]
    check(np.argmax(chip, axis=-1).tolist() == first,
          "prefill_logits does not reproduce the served first tokens")
    prompts = engine.prompt_batch(wave)
    host, host32 = _host_logits(engine.cfg, engine.params, prompts,
                                engine.max_len)
    eps = float(np.max(np.abs(host - host32)))
    tol = LOGIT_EPS_FACTOR * eps
    diff = float(np.max(np.abs(chip - host)))
    log(f"  prefill logits {chip.shape}: std {float(host.std())!r}, "
        f"host bf16 vs f32 eps {eps!r}, chip vs host bf16 max-abs {diff!r} "
        f"(tolerance {tol!r})")
    rows = np.arange(chip.shape[0])
    top_chip, top_host = chip.argmax(-1), host.argmax(-1)
    near = host[rows, top_chip] >= host.max(-1) - tol
    log(f"  top-1: chip {top_chip.tolist()} host {top_host.tolist()}; "
        f"{int((top_chip == top_host).sum())}/{len(rows)} identical, "
        f"{int(near.sum())}/{len(rows)} within tolerance of the host's max")
    check(bool(np.all(np.isfinite(chip))), "non-finite chip logits")
    check(diff <= tol, "chip prefill logits differ from the host's")
    check(bool(near.all()), "a chip top-1 token is not a host top-1 token "
          "within tolerance")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded serving path, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.backend import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    cache_events: dict[str, int] = {}

    def count(event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/"):
            cache_events[event] = cache_events.get(event, 0) + 1

    jax.monitoring.register_event_listener(count)
    log(f"platform={devices[0].platform} kind={devices[0].device_kind} "
        f"count={len(devices)} compilation_cache={cache_dir}")

    if args.chips == 4:
        phases = [("sharded serving (deepseek-moe-16b, 1x4 mesh)",
                   functools.partial(serving_phase, "deepseek-moe-16b",
                                     requests=4, slots=4, prompt_len=32,
                                     max_new=8, model_parallel=4))]
    else:
        phases = [("explorer (resnet18 / mc_hetero, prefilter on chip)",
                   explorer_phase),
                  ("serving (rwkv6-3b, published widths)",
                   functools.partial(serving_phase, "rwkv6-3b", requests=8,
                                     slots=4, prompt_len=128, max_new=32))]
    for name, run in phases:
        log(f"phase: {name}")
        t0 = time.perf_counter()
        run()
        log(f"phase ok: {name}; wall {time.perf_counter() - t0:.1f} s "
            f"(cold-run set-up incl. compilation, not a metric)")
    hits = cache_events.get("/jax/compilation_cache/cache_hits", 0)
    misses = cache_events.get("/jax/compilation_cache/cache_misses", 0)
    log(f"compilation cache: {hits} hits, {misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
