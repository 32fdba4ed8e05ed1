"""Single-model serving engine: slot-based continuous batching over the
framework's prefill/decode steps.

Requests are admitted into fixed decode slots; each slot tracks its own
position (the decode step takes per-slot position vectors), so new requests
join while others are mid-generation — continuous batching without
recompilation.  Admission zeroes the slot's recurrent state (state-space
layers) and replays the prompt but its last token through decode steps in
teacher-forcing mode; the next `step` feeds the last prompt token, and its
logits give the first token served.  So each prompt token is written once,
at its own position (for long prompts a chunked prefill would be the
production path and is noted in DESIGN.md).  The requests that
`run_until_drained` admits together replay together: dispatch ``t`` feeds
token ``t`` of every prompt that still has one, so a wave of prompts costs
as many dispatches as its longest prompt, not their sum.

Every dispatch changes the caches and states of the slots it serves alone:
the jitted step (``engine_step``) takes a position per slot, and a negative
one marks a slot the dispatch leaves bit for bit
(`repro.models.model.decode_step`'s ``update``).  The mask rides in the
position's sign so that the step keeps its four arguments (params, caches,
tokens, positions), which wrappers of ``_decode`` (the benchmark's planted
faults, the reference tests) rely on; the clamped position of an idle slot
makes each attention layer read and write back that slot's row 0.  A
replay serves the slots it replays; `step` the active slots, whose greedy
tokens are taken on the device (``greedy_tokens``) so that only the token
ids are copied back.  Admissions and decode waves are spanned
(``engine/prefill``, ``engine/step``; `repro.spans`) and counted in
`ServingEngine.stats`.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.spans import span

_log = logging.getLogger(__name__)


@dataclasses.dataclass
class Request:
    uid: int
    prompt_tokens: np.ndarray           # (L,)
    max_new_tokens: int = 16
    # filled by the engine:
    output_tokens: Optional[List[int]] = None
    n_prompt: int = 0
    done: bool = False
    t_submit: float = 0.0
    t_finish: float = 0.0
    #: terminal error state — set instead of ``done`` when the request can
    #: no longer be served (drain truncation, no available engine); a
    #: request always ends done, errored, or still owned by a live queue
    error: Optional[str] = None
    #: token-streaming hook: called with each decoded token id the moment
    #: the decode wave materializes it (same thread as the decode loop) —
    #: how a streaming front end (the gateway's SSE writer) observes
    #: first-token / per-token progress without polling `output_tokens`
    on_token: Optional[Callable[[int], None]] = None
    #: cooperative cancellation: set by the owner (e.g. a gateway handler
    #: whose client disconnected mid-stream); the engine frees the slot at
    #: the next decode wave and marks the request ``error="cancelled"``
    cancelled: bool = False


class IncompleteDrainError(RuntimeError):
    """`run_until_drained` hit ``max_steps`` with requests still pending —
    the survivors are marked ``error="incomplete_drain"`` and carried on
    the exception instead of being silently truncated."""

    def __init__(self, msg: str, *, survivors: List["Request"], steps: int):
        super().__init__(msg)
        self.survivors = survivors
        self.steps = steps


class ServingEngine:
    """Engine for one pool model (reduced config on CPU; the same step
    functions lower to the production mesh in the dry-run)."""

    def __init__(self, cfg, params=None, *, max_slots: int = 4,
                 cache_len: int = 128, seed: int = 0, greedy: bool = True):
        self.cfg = cfg
        self.params = params if params is not None else M.init_params(
            jax.random.PRNGKey(seed), cfg)
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.greedy = greedy

        self.caches = M.init_caches(cfg, max_slots, cache_len)
        self.pos = np.full((max_slots,), -1, np.int64)       # next position
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        def engine_step(params, caches, tok, pos):
            # a negative position marks a slot this dispatch does not serve
            return M.decode_step(params, cfg, caches, tok,
                                 jnp.maximum(pos, 0), update=pos >= 0)

        def greedy_tokens(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        self._decode = jax.jit(engine_step)
        self._argmax = jax.jit(greedy_tokens)
        self._reset = (jax.jit(M.reset_slot, donate_argnums=0)
                       if M.has_recurrent_state(cfg) else None)
        #: ``prefill_steps`` counts the dispatches that replay prompts and
        #: ``prefill_s`` their host seconds (resets, dispatches, until the
        #: caches are ready); ``decode_steps`` and ``decode_s`` the same for
        #: `step` (dispatch, and copy of the greedy tokens)
        self.stats = {"decode_steps": 0, "tokens_out": 0, "prefill_tokens": 0,
                      "prefill_steps": 0, "prefill_s": 0.0, "decode_s": 0.0}

    # ---- slot management ----
    def has_free_slot(self) -> bool:
        return any(r is None for r in self.slot_req)

    def admit(self, req: Request) -> bool:
        return self._admit([req]) == 1

    def _admit(self, reqs: List[Request]) -> int:
        """Admit ``reqs`` in order into free slots, as many as fit, and
        replay their prompts together.  Returns how many were admitted."""
        slots = []
        for req in reqs:
            s = next((s for s in range(self.max_slots)
                      if self.slot_req[s] is None), None)
            if s is None:
                break
            self.slot_req[s] = req
            req.output_tokens = []
            req.n_prompt = len(req.prompt_tokens)
            req.t_submit = time.time()
            self.pos[s] = 0
            slots.append(s)
        if slots:
            self._prefill_slots(slots)
        return len(slots)

    def _prefill_slots(self, slots: List[int]):
        """Zero the slots' recurrent state, then replay each prompt but its
        last token into its slot's caches: dispatch ``t`` serves the slots
        whose prompt has a token ``t`` before its last; `step` feeds the
        last tokens."""
        toks = [np.asarray(self.slot_req[s].prompt_tokens, np.int32)
                for s in slots]
        n_tok = sum(len(t) for t in toks)
        n_replay = max(len(t) for t in toks) - 1
        self.stats["prefill_tokens"] += n_tok
        t0 = time.perf_counter()
        with span("engine/prefill", engine=self.cfg.name, rows=n_tok):
            if self._reset is not None:
                for s in slots:
                    self.caches = self._reset(self.caches, s)
            for t in range(n_replay):
                # fresh host arrays per dispatch: on the CPU an uploaded
                # array may alias its numpy buffer while the step runs
                batch_tok = np.zeros((self.max_slots, 1), np.int32)
                pos_vec = np.full((self.max_slots,), -1, np.int32)
                for s, tk in zip(slots, toks):
                    if t < len(tk) - 1:
                        batch_tok[s, 0] = tk[t]
                        pos_vec[s] = t
                _, self.caches = self._decode(self.params, self.caches,
                                              jnp.asarray(batch_tok),
                                              jnp.asarray(pos_vec))
            jax.block_until_ready(self.caches)
        self.stats["prefill_steps"] += max(n_replay, 0)
        self.stats["prefill_s"] += time.perf_counter() - t0
        for s, tk in zip(slots, toks):
            self.pos[s] = len(tk) - 1

    # ---- decode wave over all active slots ----
    def step(self):
        # cancelled requests free their slots BEFORE the decode dispatch —
        # a disconnected client must not keep paying for tokens
        for s, r in enumerate(self.slot_req):
            if r is not None and r.cancelled:
                r.error = "cancelled"
                r.t_finish = time.time()
                self.slot_req[s] = None
                self.pos[s] = -1
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return
        batch_tok = np.zeros((self.max_slots, 1), np.int32)
        pos_vec = np.full((self.max_slots,), -1, np.int32)
        for s in active:
            r = self.slot_req[s]
            last = (r.output_tokens[-1] if r.output_tokens
                    else int(r.prompt_tokens[-1]))
            batch_tok[s, 0] = last
            pos_vec[s] = self.pos[s]
        t0 = time.perf_counter()
        meta = {"engine": self.cfg.name, "rows": len(active)}
        with span("engine/step", **meta):
            with span("engine/dispatch", **meta):
                logits, self.caches = self._decode(self.params, self.caches,
                                                   jnp.asarray(batch_tok),
                                                   jnp.asarray(pos_vec))
                tokens = self._argmax(logits)
            with span("engine/fetch", **meta):
                tokens = np.asarray(tokens)
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_steps"] += 1
        for s in active:
            r = self.slot_req[s]
            nxt = int(tokens[s])
            r.output_tokens.append(nxt)
            self.stats["tokens_out"] += 1
            self.pos[s] += 1
            if r.on_token is not None:
                try:
                    r.on_token(nxt)
                except Exception:
                    # a streaming consumer raising (client gone, queue torn
                    # down) must not fail the whole decode wave — the other
                    # slots' requests are unrelated traffic
                    _log.exception("on_token callback failed (uid=%s)",
                                   r.uid)
                    r.on_token = None
            if (len(r.output_tokens) >= r.max_new_tokens
                    or self.pos[s] >= self.cache_len - 1):
                r.done = True
                r.t_finish = time.time()
                self.slot_req[s] = None
                self.pos[s] = -1

    def release(self, reqs: List[Request]) -> int:
        """Evict ``reqs`` from their decode slots (freeing cache positions)
        without marking them done — the reroute path reclaims a failed
        wave's slots before handing the requests to another engine.
        Returns the number of slots freed."""
        wanted = {id(r) for r in reqs}
        freed = 0
        for s, r in enumerate(self.slot_req):
            if r is not None and id(r) in wanted:
                self.slot_req[s] = None
                self.pos[s] = -1
                freed += 1
        return freed

    def run_until_drained(self, pending: List[Request],
                          max_steps: int = 10_000) -> int:
        """Admit + decode until every request finishes (requests mark
        themselves done; the caller keeps the references).

        Hitting ``max_steps`` with work outstanding is an error, not a
        silent truncation: every survivor — still queued or mid-slot — is
        marked with a terminal ``error="incomplete_drain"`` state, evicted
        from its slot, and `IncompleteDrainError` carries the survivor
        list so the caller can reroute or report each one."""
        pending = list(pending)
        steps = 0
        while pending or any(r is not None for r in self.slot_req):
            if steps >= max_steps:
                survivors = pending + [r for r in self.slot_req
                                       if r is not None]
                for r in survivors:
                    r.error = "incomplete_drain"
                self.release(survivors)
                raise IncompleteDrainError(
                    f"engine drained {steps} steps but {len(survivors)} "
                    f"request(s) remain unfinished (max_steps={max_steps}); "
                    f"uids={[r.uid for r in survivors]}",
                    survivors=survivors, steps=steps)
            wave = []
            free = sum(r is None for r in self.slot_req)
            while pending and len(wave) < free:
                req = pending.pop(0)
                if req.cancelled:           # never admitted: no slot to free
                    req.error = "cancelled"
                    req.t_finish = time.time()
                    continue
                wave.append(req)
            self._admit(wave)
            self.step()
            steps += 1
        return steps
