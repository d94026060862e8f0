"""Hardened batched serving engine: continuous batching over a fixed-size
slot pool with admission control, invariant checks, and graceful
degradation.

Prefill fills a slot's KV rows at its own offset (per-sequence ``lengths``
make slots independent); decode advances every active slot one token per
step. The serving analogue of the paper's decoupled dispatch queue
(§III-A: Ara keeps eight instructions in flight; the engine keeps
``slots`` sequences in flight) — and, like Ara's dispatch discipline,
in-flight state is *protected*: every step runs named invariant checks
and every failure has a documented recovery policy (docs/serving.md).

Layering:

- ``serving/scheduler.py`` owns host-side admission (bounded queue,
  structured :class:`RejectReason`), deadlines/TTL, retry-with-backoff and
  the poison-request quarantine.
- This module owns the slot pool, the jitted device steps, the per-step
  invariant checks, and the degrade ladder (fp32 -> bf16 compute -> int8
  logits head via the PR-5 Policy kernels, ``kernels.ops.lm_head``).
- ``serving/faults.py`` is the bidirectional audit: every fault class
  must be *detected* by a named invariant/reject code here AND *recovered*
  per its documented policy.

Invariant codes (events in ``ServingEngine.events`` / ``counters``):

==================  ======================================================
``I_NAN_LOGITS``    finite-logits guard tripped for a slot (NaN/inf)
``I_KV_BOUNDS``     a slot's KV length left [0, max_seq] or disagrees
                    with the engine's own accounting
``I_KV_CAPACITY``   a slot reached ``max_seq`` with budget remaining
                    (retired EVICTED with partial output — never clamps)
``I_SLOT_LEAK``     a slot is marked busy by a terminal/phantom request,
                    or a free slot carries a nonzero KV length
``I_SLOT_STALL``    per-slot watchdog: no progress for ``watchdog`` ticks
==================  ======================================================

``hardened=False`` reproduces the legacy engine (no admission checks, no
invariants, no eviction — JAX index clamping corrupts the last KV row on
overflow). The fault registry uses it to prove each detector guards a
real failure mode.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.kernels import ops as kernel_ops
from repro.models import transformer as tf
from repro.models.layers import abstract_params
from repro.models.sharding import MeshCtx
from repro.serving.scheduler import (Request, RejectReason, Scheduler,
                                     State)

__all__ = ["Request", "RejectReason", "Scheduler", "State",
           "ServingEngine", "DegradeLadder", "decode_lowering"]


@dataclasses.dataclass(frozen=True)
class DegradeLadder:
    """Pressure -> decode-mode policy (graceful degradation under load).

    ``pressure = (queued + active) / slots``. Below ``bf16_at`` decode
    runs at the model's configured precision; at or above it the decode
    step switches to bfloat16 compute (the PR-1/PR-5 Policy route: params
    cast in-graph, fp32 accumulation); at or above ``int8_at`` the logits
    head additionally runs through the int8 Pallas kernel
    (``kernels.ops.lm_head`` -> ``matmul_int8``, dynamic symmetric
    quantization). Throughput-for-accuracy shedding, recorded per step in
    ``ServingEngine.counters['degraded_steps']``.
    """
    bf16_at: float = 2.0
    int8_at: float = float("inf")

    def mode_for(self, pressure: float) -> str:
        if pressure >= self.int8_at:
            return "int8"
        if pressure >= self.bf16_at:
            return "bf16"
        return "fp32"


def _mode_cfg(cfg: ArchConfig, mode: str) -> ArchConfig:
    if mode == "fp32":
        return cfg
    return dataclasses.replace(cfg, compute_dtype="bfloat16")


@functools.lru_cache(maxsize=64)
def _shared_prefill(cfg: ArchConfig, max_seq: int):
    """Batch-1 prefill on a fresh cache, shared across engine instances
    with the same (mesh-less) config — one compile per prompt shape
    process-wide, not per engine. Returns the program (it lowers as
    ``jit_serve_prefill``) and the set of prompt lengths it has seen."""
    return _prefill_program(cfg, MeshCtx(mesh=None), max_seq), set()


def _prefill_program(cfg: ArchConfig, ctx: MeshCtx, max_seq: int):
    def serve_prefill(params, tokens, *, plen):
        del plen   # static: distinguishes trace shapes
        cache = tf.init_cache(cfg, 1, max_seq, cache_dtype=jnp.float32)
        logits, _, new_cache = tf.forward(cfg, params, tokens, ctx=ctx,
                                          cache=cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, new_cache
    return jax.jit(serve_prefill, static_argnames=("plen",))


@functools.lru_cache(maxsize=64)
def _shared_decode(cfg: ArchConfig, mode: str):
    """One decode step (all slots), shared across engine instances with
    the same (mesh-less) config. ``mode`` picks the degrade rung: fp32
    (the model's configured precision), bf16 compute, or bf16 compute
    with the int8 Pallas logits head."""
    head_fn = None
    if mode == "int8":
        def head_fn(x, unembed):         # noqa: E306
            return kernel_ops.lm_head(x, unembed, compute_dtype="int8")
    return _decode_program(_mode_cfg(cfg, mode), MeshCtx(mesh=None), head_fn)


def _decode_program(mcfg: ArchConfig, ctx: MeshCtx, head_fn=None):
    """The jitted decode step; it lowers as ``jit_serve_decode``."""
    def serve_decode(params, cache, tokens, active_mask, temps, nan_mask,
                     key):
        logits, _, new_cache = tf.forward(mcfg, params, tokens, ctx=ctx,
                                          cache=cache, head_fn=head_fn)
        with jax.named_scope("sample"):
            last = logits[:, -1].astype(jnp.float32)
            # fault-injection port: a real traced input, so flipping it
            # never retraces (the mask is all-False in normal operation)
            last = jnp.where(nan_mask[:, None], jnp.float32(jnp.nan), last)
            finite = jnp.all(jnp.isfinite(last), axis=-1)
            greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
            scaled = last / jnp.maximum(temps, 1e-6)[:, None]
            keys = jax.random.split(key, last.shape[0])
            sampled = jax.vmap(jax.random.categorical)(keys, scaled) \
                .astype(jnp.int32)
            next_tok = jnp.where(temps > 0, sampled, greedy)
        # inactive slots must not advance their lengths
        new_cache["lengths"] = jnp.where(active_mask,
                                         new_cache["lengths"],
                                         cache["lengths"])
        return next_tok, finite, new_cache
    return jax.jit(serve_decode)


def decode_lowering(cfg: ArchConfig, slots: int, max_seq: int,
                    sharding=None):
    """The engine's decode step (model precision, fp32 cache) lowered from
    shapes alone: ``.compile().memory_analysis()`` sizes a slot pool before
    any weights exist. The step donates nothing, so its output cache is a
    second buffer on top of the arguments. ``sharding`` places the shapes
    (e.g. on a described device for an ahead-of-time compile)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def like(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params = like(abstract_params(tf.model_template(cfg),
                                  jnp.dtype(cfg.param_dtype)))
    cache = like(tf.init_cache(cfg, slots, max_seq, abstract=True,
                               cache_dtype=jnp.float32))
    return _shared_decode(cfg, "fp32").lower(
        params, cache, sds((slots, 1), jnp.int32), sds((slots,), bool),
        sds((slots,), jnp.float32), sds((slots,), bool),
        sds((2,), jnp.uint32))


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_seq: int = 512, ctx: Optional[MeshCtx] = None,
                 greedy: bool = True, hardened: bool = True,
                 max_queue: int = 256, max_retries: int = 2,
                 watchdog: int = 8, degrade: Optional[DegradeLadder] = None,
                 scheduler: Optional[Scheduler] = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.ctx = ctx or MeshCtx(mesh=None)
        self.greedy = greedy
        self.hardened = hardened
        self.watchdog = watchdog
        self.degrade = degrade
        self.cache = tf.init_cache(cfg, slots, max_seq,
                                   cache_dtype=jnp.float32)
        self.active: Dict[int, Request] = {}     # slot -> request
        self.sched = scheduler or Scheduler(
            slots=slots, max_seq=max_seq, max_queue=max_queue,
            max_retries=max_retries)
        self.tick = 0
        self.events: List[dict] = []             # named detections
        self.counters = self.sched.counters      # one shared counter set
        self.finished: List[Request] = []        # all terminal requests
        # fault-injection surface (serving/faults.py)
        self.fault_hooks: List[Callable[["ServingEngine"], None]] = []
        self._inject_nan_slots: Set[int] = set()
        self._suppress_slots: Set[int] = set()
        # per-slot host accounting (the invariant checks' ground truth)
        self._slot_len: Dict[int, int] = {}
        self._slot_progress: Dict[int, int] = {}
        self._key = jax.random.PRNGKey(0)
        self._decode_fns: Dict[str, Callable] = {}
        self._prefill = None

    # -- legacy-compatible queue view ---------------------------------------

    @property
    def queue(self):
        return self.sched.queue

    # -- device fns ----------------------------------------------------------

    def _decode_for(self, mode: str):
        fn = self._decode_fns.get(mode)
        if fn is None:
            if self.ctx.mesh is None:
                fn = _shared_decode(self.cfg, mode)
            else:                        # mesh engines keep their own jit
                fn = _decode_program(_mode_cfg(self.cfg, mode), self.ctx)
            self._decode_fns[mode] = fn
        return fn

    def _prefill_one(self, tokens, plen):
        if self._prefill is None:
            if self.ctx.mesh is None:
                self._prefill = _shared_prefill(self.cfg, self.max_seq)
            else:
                self._prefill = (_prefill_program(self.cfg, self.ctx,
                                                  self.max_seq), set())
        fn, seen = self._prefill
        self.counters["prefills"] += 1
        if plen not in seen:
            seen.add(plen)
            self.counters["prefill_new_shapes"] += 1
        return fn(self.params, tokens, plen=plen)

    @staticmethod
    def _batch_dim(key: str) -> int:
        return 0 if key in ("lengths", "memory") else 1

    def _scatter_slot(self, pool: dict, single: dict, slot: int) -> dict:
        out = {}
        for k, v in pool.items():
            bd = self._batch_dim(k)
            row = jnp.take(single[k], 0, axis=bd)
            if bd == 0:
                out[k] = v.at[slot].set(row.astype(v.dtype))
            else:
                out[k] = v.at[:, slot].set(row.astype(v.dtype))
        return out

    # -- bookkeeping helpers -------------------------------------------------

    def _event(self, code: str, **detail):
        self.events.append({"tick": self.tick, "code": code, **detail})
        self.counters[code] += 1

    def _set_length(self, slot: int, value: int):
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(value)

    def _free_slot(self, slot: int):
        self.active.pop(slot, None)
        self._slot_len.pop(slot, None)
        self._slot_progress.pop(slot, None)
        self._set_length(slot, 0)

    def _finish(self, slot: Optional[int], req: Request, state: State,
                reason: str, finished: List[Request]):
        req.finish(state, self.tick, reason)
        if slot is not None:
            self._free_slot(slot)
        finished.append(req)
        self.finished.append(req)

    def _retry_or_quarantine(self, slot: int, req: Request, cause: str,
                             finished: List[Request]):
        """Recovery policy for transient step failures: evict the slot,
        requeue with backoff; quarantine after max_retries."""
        self._free_slot(slot)
        if not self.sched.requeue(req, self.tick, cause):
            finished.append(req)
            self.finished.append(req)

    # -- invariant checks ----------------------------------------------------

    def _audit_slots(self, finished: List[Request]):
        """Host-side slot/KV consistency: the I_SLOT_LEAK and I_KV_BOUNDS
        detectors. Runs before admission so reclaimed capacity is reusable
        in the same step."""
        with obs.span("serve.audit"):
            with obs.span("serve.audit.wait"):
                lengths = np.asarray(self.cache["lengths"])
            self.counters["host_syncs"] += 1
            self._audit_lengths(lengths, finished)

    def _audit_lengths(self, lengths: np.ndarray, finished: List[Request]):
        for slot in list(self.active):
            req = self.active[slot]
            if req is None or req.state.terminal():
                self._event("I_SLOT_LEAK", slot=slot,
                            detail="terminal/phantom request holds a slot")
                self._free_slot(slot)
                continue
            expect = self._slot_len.get(slot)
            actual = int(lengths[slot])
            if expect is None or actual != expect \
                    or not (0 <= actual <= self.max_seq):
                self._event("I_KV_BOUNDS", slot=slot, uid=req.uid,
                            expected=expect, actual=actual)
                self._retry_or_quarantine(slot, req, "kv-bounds", finished)
        for slot in range(self.slots):
            if slot not in self.active and int(lengths[slot]) != 0:
                self._event("I_SLOT_LEAK", slot=slot,
                            detail="free slot with nonzero KV length")
                self._set_length(slot, 0)

    # -- host scheduling -----------------------------------------------------

    def submit(self, req: Request) -> Optional[RejectReason]:
        """Admit to the bounded queue; returns the structured reject
        reason (also recorded on ``req``) or None on acceptance. The
        legacy engine (``hardened=False``) accepts everything."""
        if not self.hardened:
            req.submit_tick = self.tick
            req.t_submit = time.perf_counter()
            self.sched.queue.append(req)
            return None
        return self.sched.submit(req, self.tick)

    def _admit(self, finished: List[Request]):
        for slot in range(self.slots):
            if slot in self.active:
                continue
            req = self.sched.next_ready(self.tick) if self.hardened else (
                self.sched.queue.popleft() if self.sched.queue else None)
            if req is None:
                return
            plen = len(req.prompt)
            if self.hardened and plen > self.max_seq:
                # defense in depth: submit() already rejects this
                self._finish(None, req, State.REJECTED,
                             RejectReason.PROMPT_TOO_LONG.value, finished)
                continue
            with obs.span("serve.admit", uid=req.uid, plen=plen):
                tok = self._prefill_into(slot, req)
            req.out_tokens.append(tok)
            req.t_tokens.append(req.t_first)
            req.first_token_tick = self.tick
            self._slot_len[slot] = plen
            self._slot_progress[slot] = self.tick
            self.active[slot] = req
            req.state = State.DECODE
            # budget of 1 / instant eos: done without holding the slot
            if tok == req.eos_id or len(req.out_tokens) >= req.max_new_tokens:
                self._finish(slot, req, State.DONE, "", finished)
            elif self.hardened and plen >= self.max_seq:
                self._event("I_KV_CAPACITY", slot=slot, uid=req.uid,
                            length=plen)
                self._finish(slot, req, State.EVICTED, "I_KV_CAPACITY",
                             finished)

    def _prefill_into(self, slot: int, req: Request) -> int:
        """Prefill ``req`` on its own, scatter its cache into ``slot``;
        returns the first token, held on the host."""
        req.t_admit = time.perf_counter()
        req.state = State.PREFILL
        with obs.span("serve.prefill.dispatch"):
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            next_tok, single = self._prefill_one(toks, len(req.prompt))
        with obs.span("serve.scatter.dispatch"):
            self.cache = self._scatter_slot(self.cache, single, slot)
        with obs.span("serve.prefill.wait"):
            tok = int(next_tok[0])
        self.counters["host_syncs"] += 1
        req.t_first = time.perf_counter()
        return tok

    def _pick_mode(self) -> str:
        if self.degrade is None:
            return "fp32"
        mode = self.degrade.mode_for(self.sched.pressure(len(self.active)))
        if mode != "fp32":
            self.counters["degraded_steps"] += 1
            self.counters[f"degraded_steps_{mode}"] += 1
        return mode

    def _decode_step(self, finished: List[Request]):
        self.counters["decode_steps"] += 1
        with obs.span("serve.decode.prepare"):
            tokens = np.zeros((self.slots, 1), np.int32)
            mask = np.zeros((self.slots,), bool)
            temps = np.zeros((self.slots,), np.float32)
            nan_mask = np.zeros((self.slots,), bool)
            for slot, req in self.active.items():
                tokens[slot, 0] = req.out_tokens[-1] if req.out_tokens else 0
                mask[slot] = slot not in self._suppress_slots
                temps[slot] = req.temperature
                nan_mask[slot] = slot in self._inject_nan_slots
            self._inject_nan_slots.clear()
            self._key, sub = jax.random.split(self._key)
            mode = self._pick_mode()
            decode = self._decode_for(mode)
        with obs.span("serve.decode.dispatch", mode=mode):
            next_tok, finite, self.cache = decode(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(mask), jnp.asarray(temps), jnp.asarray(nan_mask),
                sub)
        with obs.span("serve.decode.wait"):
            next_tok = np.asarray(next_tok)
            finite = np.asarray(finite)
        self.counters["host_syncs"] += 2
        with obs.span("serve.decode.retire"):
            self._retire(mask, next_tok, finite, finished)

    def _retire(self, mask: np.ndarray, next_tok: np.ndarray,
                finite: np.ndarray, finished: List[Request]):
        held = time.perf_counter()
        for slot, req in list(self.active.items()):
            if not mask[slot]:
                pass                      # suppressed: no progress made
            elif self.hardened and not finite[slot]:
                self._event("I_NAN_LOGITS", slot=slot, uid=req.uid)
                self._retry_or_quarantine(slot, req, "nan-logits", finished)
                continue
            else:
                tok = int(next_tok[slot])
                req.out_tokens.append(tok)
                req.t_tokens.append(held)
                self._slot_len[slot] += 1
                self._slot_progress[slot] = self.tick
                if tok == req.eos_id \
                        or len(req.out_tokens) >= req.max_new_tokens:
                    self._finish(slot, req, State.DONE, "", finished)
                    continue
                dl = req.deadline_tick() if self.hardened else None
                if dl is not None and self.tick >= dl:
                    self._finish(slot, req, State.TIMED_OUT,
                                 "T_DEADLINE_EXPIRED", finished)
                    self.counters["T_DEADLINE_EXPIRED"] += 1
                    continue
                if self.hardened and self._slot_len[slot] >= self.max_seq:
                    self._event("I_KV_CAPACITY", slot=slot, uid=req.uid,
                                length=self._slot_len[slot])
                    self._finish(slot, req, State.EVICTED, "I_KV_CAPACITY",
                                 finished)
                    continue
            if self.hardened and slot in self.active and \
                    self.tick - self._slot_progress[slot] >= self.watchdog:
                self._event("I_SLOT_STALL", slot=slot, uid=req.uid,
                            stalled=self.tick - self._slot_progress[slot])
                self._retry_or_quarantine(slot, req, "slot-stall", finished)

    def step(self) -> List[Request]:
        """One engine step: run fault hooks, maintain the queue (deadline
        sheds), audit slot invariants, admit, decode one token for every
        active slot, retire. Returns requests that reached a terminal
        state this step (DONE / EVICTED / TIMED_OUT / FAILED).

        The step is the root span ``serve.step`` (attrs ``tick``,
        ``active`` after admission and ``admitted``)."""
        self.tick += 1
        self.counters["steps"] += 1
        with obs.span("serve.step", tick=self.tick) as sp:
            for hook in list(self.fault_hooks):
                hook(self)
            finished: List[Request] = []
            if self.hardened:
                with obs.span("serve.sched"):
                    for req in self.sched.tick(self.tick):
                        finished.append(req)
                        self.finished.append(req)
                self._audit_slots(finished)
            before = self.counters["prefills"]
            self._admit(finished)
            sp.attrs["active"] = len(self.active)
            sp.attrs["admitted"] = self.counters["prefills"] - before
            if self.active:
                self._decode_step(finished)
        return finished

    def run_to_completion(self, max_steps: int = 1000) -> List[Request]:
        done = []
        for _ in range(max_steps):
            done += self.step()
            if not self.active and not self.sched.queue:
                break
        return done

    def stats(self) -> dict:
        states = {}
        for r in self.finished:
            states[r.state.value] = states.get(r.state.value, 0) + 1
        return {"tick": self.tick, "active": len(self.active),
                "finished_states": states, "events": len(self.events),
                **self.sched.stats()}
