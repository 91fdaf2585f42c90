"""Batched serving engine: continuous prefill + decode over a KV cache.

A deliberately compact vLLM-style loop: requests are admitted into a fixed
batch of slots; prefill fills a slot's cache region; every engine step
decodes one token for all active slots. Caches live donated on device; the
decode step is a single jit'd program (one serve_step per token).

Admission is delegated to `repro.serve.batching.SlotBatcher` — the same
policy object the analytic simulator (`repro.serve.simulator`) drives —
so the occupancy invariants the SLO curves assume are the invariants the
engine executes.  One engine-specific restriction: the KV cache shares a
single sequence clock (`cur_len`) across slots, so `serve` admits in FIFO
waves (newcomers enter when the current cohort has fully drained) rather
than per-step.  The simulator's per-step admission is therefore an upper
bound the engine approaches as decode-length variance shrinks.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import zoo
from repro.models.module import init_sharded
from repro.serve.batching import SlotBatcher


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (S,) token ids
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, mesh, batch_slots: int = 4,
                 max_len: int = 512, prompt_len: int = 64):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.B = batch_slots
        self.max_len = max_len
        self.prompt_len = prompt_len
        self._cache_specs = zoo.build_cache_specs(cfg, batch_slots, max_len)
        self.caches = self._new_caches()
        self.cur_len = 0
        self.slots: list[Request | None] = [None] * batch_slots

        def _prefill(params, batch, caches):
            return zoo.prefill(cfg, params, batch, caches, mesh=mesh)

        def _decode(params, tokens, caches, cur_len):
            return zoo.decode_step(cfg, params, tokens, caches, cur_len,
                                   mesh=mesh)

        self._prefill = jax.jit(_prefill, donate_argnums=(2,))
        self._decode = jax.jit(_decode, donate_argnums=(2,))

    def _new_caches(self):
        """Zeroed caches placed by their sharding rules on the mesh."""
        return init_sharded(self._cache_specs, jax.random.PRNGKey(0),
                            self.mesh)

    def prompt_batch(self, requests: list[Request]) -> np.ndarray:
        """(batch_slots, prompt_len) token ids: each request's prompt
        left-padded (or truncated to its tail) in its slot."""
        assert len(requests) <= self.B
        S = self.prompt_len
        prompts = np.zeros((self.B, S), np.int32)
        for i, r in enumerate(requests):
            p = r.prompt[-S:]
            prompts[i, S - len(p):] = p
        return prompts

    # ---- step methods (one jit'd program each) -----------------------
    def prefill_step(self, requests: list[Request]):
        """Batched prefill for up to `batch_slots` requests: fills each
        slot's cache region, resets the sequence clock to `prompt_len`,
        and returns the first greedily sampled token per slot."""
        logits, self.caches = self._prefill(
            self.params, {"tokens": jnp.asarray(self.prompt_batch(requests))},
            self.caches)
        self.cur_len = self.prompt_len
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def prefill_logits(self, requests: list[Request]):
        """Last-token logits of the same compiled prefill on fresh caches,
        leaving the engine's state untouched: the served program's output
        for comparison against a reference."""
        with jax.set_mesh(self.mesh):
            logits, _ = self._prefill(
                self.params,
                {"tokens": jnp.asarray(self.prompt_batch(requests))},
                self._new_caches())
        return logits

    def decode_once(self, tok):
        """One decode step for every slot: consumes the previous token
        per slot, advances the shared sequence clock, returns the next
        greedily sampled token per slot."""
        logits, self.caches = self._decode(
            self.params, tok[:, None], self.caches, jnp.int32(self.cur_len))
        self.cur_len += 1
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------------
    def run(self, requests: list[Request], greedy: bool = True):
        """Serve a batch of requests to completion (batched prefill+decode)."""
        assert len(requests) <= self.B
        with jax.set_mesh(self.mesh):
            tok = self.prefill_step(requests)
            max_new = max(r.max_new_tokens for r in requests)
            for step in range(max_new):
                for i, r in enumerate(requests):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(tok[i]))
                tok = self.decode_once(tok)
        for r in requests:
            r.done = True
        return requests

    def serve(self, requests: list[Request]):
        """Serve arbitrarily many requests through the slot pool.

        FIFO admission through a `SlotBatcher`: up to `batch_slots`
        requests form a wave (one batched prefill), each drains its slot
        when it reaches `max_new_tokens`, and the next wave is admitted
        once the cohort is empty (shared-clock restriction, see module
        docstring).  Tokens are bit-identical to `run` on each wave.
        """
        batcher = SlotBatcher(self.B)
        queue = list(range(len(requests)))
        with jax.set_mesh(self.mesh):
            while queue:
                n_admit = min(batcher.free_slots(), len(queue))
                cohort = [queue.pop(0) for _ in range(n_admit)]
                for rid in cohort:
                    batcher.admit(rid)
                reqs = [requests[rid] for rid in cohort]
                tok = self.prefill_step(reqs)
                while batcher.active():
                    for slot, rid in enumerate(cohort):
                        r = requests[rid]
                        if r.done:
                            continue
                        r.out_tokens.append(int(tok[slot]))
                        if len(r.out_tokens) >= r.max_new_tokens:
                            r.done = True
                            batcher.release(rid)
                    if batcher.active():
                        tok = self.decode_once(tok)
        self.max_active = batcher.max_active
        return requests
