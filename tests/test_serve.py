"""Serving engine: token-level goldens for batched prefill + decode.

The engine's jit'd loop (donated caches, one program per phase) must
produce token-for-token the same greedy decode as a plain eager
reference loop over `zoo.prefill`/`zoo.decode_step` — not just the right
shapes.  `serve` (continuous batching through `SlotBatcher`) must match
`run` on each admission wave and drain arbitrarily many requests.
"""
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_config
from repro.models import zoo
from repro.models.module import init_from_specs
from repro.models.zoo import build_param_specs
from repro.serve.engine import Request, ServeEngine


def _setup(batch_slots, prompt_len, max_len, mesh_shape=(1, 1)):
    cfg = reduce_config(ARCHS["llama3.2-3b"])
    params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0))
    mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    engine = ServeEngine(cfg, params, mesh=mesh, batch_slots=batch_slots,
                         max_len=max_len, prompt_len=prompt_len)
    return cfg, params, mesh, engine


def _reference_tokens(cfg, params, mesh, prompts, max_new, max_len):
    """Eager (un-jitted) greedy decode: the token-level golden."""
    B, S = prompts.shape
    caches = init_from_specs(zoo.build_cache_specs(cfg, B, max_len),
                             jax.random.PRNGKey(0))
    outs = [[] for _ in range(B)]
    with jax.set_mesh(mesh):
        logits, caches = zoo.prefill(cfg, params,
                                     {"tokens": jnp.asarray(prompts)},
                                     caches, mesh=mesh)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cur_len = S
        for _ in range(max_new):
            for i in range(B):
                outs[i].append(int(tok[i]))
            logits, caches = zoo.decode_step(cfg, params, tok[:, None],
                                             caches, jnp.int32(cur_len),
                                             mesh=mesh)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cur_len += 1
    return outs


def test_run_matches_eager_reference_token_for_token():
    cfg, params, mesh, engine = _setup(batch_slots=2, prompt_len=16,
                                       max_len=48)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(2, 16)).astype(np.int32)
    golden = _reference_tokens(cfg, params, mesh, prompts, max_new=6,
                               max_len=48)
    reqs = [Request(prompt=prompts[i], max_new_tokens=6) for i in range(2)]
    engine.run(reqs)
    for r, want in zip(reqs, golden):
        assert r.done
        assert r.out_tokens == want       # token-level, not shape-level
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)


def test_run_respects_per_request_lengths():
    cfg, params, mesh, engine = _setup(batch_slots=2, prompt_len=16,
                                       max_len=48)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(2, 16)).astype(np.int32)
    golden = _reference_tokens(cfg, params, mesh, prompts, max_new=6,
                               max_len=48)
    reqs = [Request(prompt=prompts[0], max_new_tokens=3),
            Request(prompt=prompts[1], max_new_tokens=6)]
    engine.run(reqs)
    # the short request is a prefix of the long schedule's golden tokens
    assert reqs[0].out_tokens == golden[0][:3]
    assert reqs[1].out_tokens == golden[1]


def test_engine_determinism():
    cfg, params, mesh, _ = _setup(batch_slots=1, prompt_len=16, max_len=48)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab, size=16)
    outs = []
    for _ in range(2):
        engine = ServeEngine(cfg, params, mesh=mesh, batch_slots=1,
                             max_len=48, prompt_len=16)
        req = Request(prompt=prompt, max_new_tokens=5)
        engine.run([req])
        outs.append(tuple(req.out_tokens))
    assert outs[0] == outs[1]


def test_serve_waves_match_run():
    # 4 requests through 2 slots: serve() must emit, wave by wave,
    # exactly the tokens run() produces for each 2-request batch
    cfg, params, mesh, engine = _setup(batch_slots=2, prompt_len=16,
                                       max_len=48)
    rng = np.random.default_rng(2)
    prompts = rng.integers(1, cfg.vocab, size=(4, 16)).astype(np.int32)
    reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    engine.serve(reqs)
    assert engine.max_active == 2
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
    for lo in (0, 2):
        fresh = ServeEngine(cfg, params, mesh=mesh, batch_slots=2,
                            max_len=48, prompt_len=16)
        wave_reqs = [Request(prompt=p, max_new_tokens=4)
                     for p in prompts[lo:lo + 2]]
        fresh.run(wave_reqs)
        for served, ran in zip(reqs[lo:lo + 2], wave_reqs):
            assert served.out_tokens == ran.out_tokens


def test_serve_on_multi_device_mesh():
    cfg, params, mesh, engine = _setup(batch_slots=2, prompt_len=16,
                                       max_len=48, mesh_shape=(2, 2))
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=16),
                    max_new_tokens=4) for _ in range(3)]
    engine.serve(reqs)
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
    assert all(0 <= t < cfg.vocab for r in reqs for t in r.out_tokens)


def test_prefill_logits_reproduce_served_first_tokens():
    """`prefill_logits` reruns the engine's own compiled prefill on fresh
    caches: its argmax is the first token each request of the wave got."""
    cfg, params, mesh, engine = _setup(batch_slots=2, prompt_len=16,
                                       max_len=48)
    rng = np.random.default_rng(4)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=16),
                    max_new_tokens=3) for _ in range(3)]
    engine.serve(reqs)
    logits = engine.prefill_logits(reqs[:2])
    assert logits.shape == (2, cfg.vocab)
    assert np.asarray(jnp.argmax(logits, axis=-1)).tolist() == \
        [r.out_tokens[0] for r in reqs[:2]]


@pytest.mark.parametrize("flags, reduced", [([], False), (["--smoke"], True)],
                         ids=["published", "smoke"])
def test_cli_serves_published_widths_unless_smoke(flags, reduced):
    """Parsing only: without `--smoke` the CLI serves the config at its
    published widths."""
    from repro.launch import serve
    cfg = serve.engine_config(serve.parse_args(["--arch", "rwkv6-3b",
                                                *flags]))
    full = ARCHS["rwkv6-3b"]
    assert cfg == (reduce_config(full) if reduced else full)
