"""LM continuous batching: slot-level request scheduling over a shared cache.

Production serving keeps every batch slot busy: when one sequence finishes,
the next queued request is admitted into its slot immediately - prompts
stream through the same per-token decode step (teacher-forced) while
neighbouring slots keep generating.  This needs per-slot positions (each
sequence is at its own offset), which `attention_decode` supports natively,
plus per-slot cache invalidation on admission (`reset_slots`: attention
validity masks already exclude entries past the new position; recurrent
SSM/RG-LRU states are zeroed explicitly).

The host loop does slot bookkeeping; the per-token step stays one jitted
SPMD program - the standard split in production engines.  The LM half
moved here with the rest of the retired `serve.Engine` surface.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tr


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_new: int


def _batch_axis(path) -> int:
    return 1 if any(str(getattr(p, "key", "")) == "blocks" for p in path) else 0


def reset_slots(cache, mask: jnp.ndarray):
    """Zero the cache state of slots where mask[b] is True."""

    def one(path, leaf):
        ax = _batch_axis(path)
        shape = [1] * leaf.ndim
        shape[ax] = mask.shape[0]
        m = mask.reshape(shape)
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    return jax.tree_util.tree_map_with_path(one, cache)


class ContinuousBatchingEngine:
    """Greedy continuous-batching server with `n_slots` parallel lanes."""

    def __init__(self, cfg: ModelConfig, params, n_slots: int,
                 max_len: int, eos_id: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self._cache = tr.init_cache(n_slots, max_len, cfg, dtype=jnp.float32)

        def step(params, cache, tokens_t, pos):
            logits, cache = tr.decode_step(params, cache, tokens_t, pos, cfg)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        self._step = jax.jit(step, donate_argnums=(1,))
        self._reset = jax.jit(reset_slots)

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Serve all requests to completion; returns generated ids per req."""
        queue = list(requests)
        out: Dict[int, List[int]] = {r.req_id: [] for r in requests}
        # host-side slot state
        slot_req: List[Optional[Request]] = [None] * self.n_slots
        pos = np.zeros(self.n_slots, np.int32)
        cur = np.zeros(self.n_slots, np.int32)
        n_gen = np.zeros(self.n_slots, np.int32)
        cache = self._cache

        def admit(s):
            nonlocal cache
            if not queue:
                slot_req[s] = None
                return False
            req = queue.pop(0)
            slot_req[s] = req
            pos[s] = 0
            cur[s] = req.prompt[0]
            n_gen[s] = 0
            mask = jnp.asarray(np.arange(self.n_slots) == s)
            cache = self._reset(cache, mask)
            return True

        for s in range(self.n_slots):
            admit(s)

        while any(r is not None for r in slot_req):
            nxt, cache = self._step(self.params, cache,
                                    jnp.asarray(cur), jnp.asarray(pos))
            nxt = np.asarray(nxt)
            for s, req in enumerate(slot_req):
                if req is None:
                    continue
                in_prompt = pos[s] + 1 < len(req.prompt)
                if in_prompt:                      # stream the prompt
                    cur[s] = req.prompt[pos[s] + 1]
                else:                              # generating
                    tok = int(nxt[s])
                    out[req.req_id].append(tok)
                    n_gen[s] += 1
                    cur[s] = tok
                    done = (n_gen[s] >= req.max_new
                            or (self.eos_id is not None
                                and tok == self.eos_id)
                            or pos[s] + 2 >= self.max_len)
                    if done:
                        admit(s)
                        continue
                pos[s] += 1
        return out
