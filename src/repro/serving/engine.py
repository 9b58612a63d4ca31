"""Serving engines: request-level continuous batching plus the legacy
static-batch engine.

``ContinuousBatchingEngine`` is the serving core: a persistent decode
batch of ``max_slots`` rows (slot-based KV/SSM state, per-row cache
indices), an iteration-level scheduler that interleaves (optionally
chunked) prefill with running decode steps, and request
admission/eviction with no full-batch barrier.  Time comes either from
wall-clock measurement or from a per-phase hybrid-CPU cost model
(:class:`~repro.serving.phases.HybridPhaseCost`), which also drives the
paper's control loop with separate "prefill" / "decode" ratio keys.

``ServeEngine`` (static shapes, whole-batch generate) remains for
benchmarks and as the building block the compatibility layer is
constructed from.  ``RoutedServer.serve_batch`` is now a thin wrapper
over per-replica continuous-batching engines: it keeps the seed-era
signature (proportional split, capacity clamp, ``times_override``) while
executing through the new request path.  New callers should use
:class:`~repro.serving.dispatch.InflightDispatcher` instead, which routes
individual requests by measured per-phase replica throughput.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import events as _ev
from repro.device import committed_device
from repro.models import forward, init_state
from repro.models.attention import KVCache
from repro.runtime import (
    Balancer,
    DeviceRuntime,
    Plan,
    ReplicaRouter,
    StatsSink,
    clamp_to_capacity,
)

from .phases import DECODE, PHASE_ISA, PREFILL, phase_kernel_key
from .request import FinishReason, Request, RequestState
from .scheduler import IterationScheduler, IterationStats
from .slots import SlotCacheManager


def _stack_lane_states(states):
    """Stack per-lane batch-1 states into one B-row state pytree.

    Every leaf carries the period-repeat axis first and the batch axis
    second, so generic leaves concatenate along axis 1; KV caches need the
    per-row index form — ``idx`` goes from (n_rep,) scalar-per-repeat to
    (n_rep, B), the slot-batched convention ``attn_fwd`` already supports
    (each lane appends at its own offset)."""

    def comb(*leaves):
        if isinstance(leaves[0], KVCache):
            return KVCache(
                k=jnp.concatenate([l.k for l in leaves], axis=1),
                v=jnp.concatenate([l.v for l in leaves], axis=1),
                idx=jnp.stack([l.idx for l in leaves], axis=1))
        return jnp.concatenate(leaves, axis=1)

    return jax.tree.map(comb, *states,
                        is_leaf=lambda x: isinstance(x, KVCache))


def _slice_lane_state(stacked, i: int):
    """Row ``i`` of a lane-stacked state, back in batch-1 form (KV ``idx``
    returns to its (n_rep,) scalar-per-repeat shape, so the row is adopt-
    and restack-compatible with states from :func:`init_state`)."""

    def pick(leaf):
        if isinstance(leaf, KVCache):
            return KVCache(k=leaf.k[:, i:i + 1], v=leaf.v[:, i:i + 1],
                           idx=leaf.idx[:, i])
        return leaf[:, i:i + 1]

    return jax.tree.map(pick, stacked,
                        is_leaf=lambda x: isinstance(x, KVCache))


@dataclass
class GenerationResult:
    tokens: np.ndarray        # (B, prompt+new) — B may include padding rows
    prefill_seconds: float
    decode_seconds: float
    steps: int
    n_requests: Optional[int] = None   # real (unpadded) request count

    @property
    def tokens_per_second(self) -> float:
        b = self.n_requests if self.n_requests is not None else self.tokens.shape[0]
        new = b * self.steps
        return new / max(self.decode_seconds, 1e-9)


class ServeEngine:
    """One replica: static-shape batched greedy decoding."""

    def __init__(self, cfg: ModelConfig, params, *, batch_size: int,
                 max_seq: int, donate_state: bool = True):
        self.cfg = cfg
        self.params = params
        self.batch_size = batch_size
        self.max_seq = max_seq

        @jax.jit
        def _prefill(params, tokens, state):
            out = forward(cfg, params, tokens, state=state, pos_offset=0,
                          logits_mode="last")
            return out.logits[:, -1, :], out.state

        donate = (2,) if donate_state else ()

        @functools.partial(jax.jit, donate_argnums=donate)
        def _decode(params, tok, state, offset):
            out = forward(cfg, params, tok, state=state, pos_offset=offset)
            return out.logits[:, -1, :], out.state

        self._prefill = _prefill
        self._decode = _decode

    def fresh_state(self):
        return init_state(self.cfg, self.batch_size, self.max_seq)

    def generate(self, prompts: jax.Array, n_steps: int,
                 sampler: Optional[Callable] = None,
                 n_requests: Optional[int] = None) -> GenerationResult:
        """prompts: (B, S0) int32.  Greedy unless ``sampler(logits)->tok``.
        ``n_requests`` is the real request count when rows are padding."""
        b, s0 = prompts.shape
        assert b == self.batch_size
        state = self.fresh_state()

        t0 = time.perf_counter()
        logits, state = self._prefill(self.params, prompts, state)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0

        pick = sampler or (lambda lg: jnp.argmax(lg, -1)[:, None])
        toks = [np.asarray(prompts)]
        tok = pick(logits)
        t1 = time.perf_counter()
        for i in range(n_steps):
            toks.append(np.asarray(tok))
            logits, state = self._decode(self.params, tok, state,
                                         jnp.asarray(s0 + i, jnp.int32))
            tok = pick(logits)
        tok.block_until_ready()
        t_decode = time.perf_counter() - t1
        return GenerationResult(
            tokens=np.concatenate(toks, axis=1),
            prefill_seconds=t_prefill,
            decode_seconds=t_decode,
            steps=n_steps,
            n_requests=n_requests,
        )


def step_programs(cfg: ModelConfig, *, trunk=None, apply_head: bool = True,
                  donate_state: bool = True):
    """The engine's three step programs for ``cfg``: ``(prefill,
    prefill_lanes, decode)``.

    They are jitted unless the balanced trunk runs eagerly; decode donates
    its state argument.  With a compiled trunk every program takes the
    device offset snapshot as its last argument and returns the traced cost
    tape as an extra output.  Module-level so that the programs can be
    lowered for a chip without building an engine (and its state)."""
    # Tracing-disallowed fallback: a trunk built with jit_bridge=False
    # runs its shard dispatches eagerly, so the step functions must
    # not be jitted (the io_callback bridge would otherwise trace).
    use_jit = trunk is None or trunk.jit_bridge
    # Compiled trunk: the step functions take the device offset
    # snapshot as an extra argument, apply the balanced head in-graph,
    # and return the traced cost tape as an extra output — zero host
    # callbacks inside the step; ratio feedback + offset refresh run
    # between steps (see repro.kernels.compiled).
    compiled = trunk is not None and getattr(trunk, "mode", None) == "compiled"

    donate = (2,) if donate_state and use_jit else ()

    if compiled:
        def _head_in_graph(logits, phase, offsets):
            if trunk.head is None:
                return logits
            return trunk.apply_head(logits, isa=PHASE_ISA[phase],
                                    offsets=offsets)

        def _prefill(params, tokens, state, offset, offsets):
            tape = trunk.compiled_tape_begin()
            out = forward(cfg, params, tokens, state=state,
                          pos_offset=offset, logits_mode="last",
                          apply_head=apply_head, trunk=trunk,
                          trunk_isa=PHASE_ISA[PREFILL],
                          trunk_offsets=offsets)
            logits = _head_in_graph(out.logits[:, -1, :], PREFILL,
                                    offsets)
            return logits, out.state, trunk.compiled_tape_end(tape)

        def _decode(params, tok, state, pos, offsets):
            tape = trunk.compiled_tape_begin()
            out = forward(cfg, params, tok, state=state, pos_offset=pos,
                          apply_head=apply_head, trunk=trunk,
                          trunk_isa=PHASE_ISA[DECODE],
                          trunk_offsets=offsets)
            logits = _head_in_graph(out.logits[:, -1, :], DECODE,
                                    offsets)
            return logits, out.state, trunk.compiled_tape_end(tape)

        def _prefill_lanes_fn(params, tokens, states, offsets, snap):
            tape = trunk.compiled_tape_begin()
            stacked = _stack_lane_states(states)
            out = forward(cfg, params, tokens, state=stacked,
                          pos_offset=offsets, logits_mode="last",
                          apply_head=apply_head, trunk=trunk,
                          trunk_isa=PHASE_ISA[PREFILL],
                          trunk_offsets=snap)
            rows = [_slice_lane_state(out.state, i)
                    for i in range(len(states))]
            logits = _head_in_graph(out.logits[:, -1, :], PREFILL, snap)
            return logits, rows, trunk.compiled_tape_end(tape)
    else:
        def _prefill(params, tokens, state, offset):
            out = forward(cfg, params, tokens, state=state,
                          pos_offset=offset, logits_mode="last",
                          apply_head=apply_head,
                          trunk=trunk, trunk_isa=PHASE_ISA[PREFILL])
            return out.logits[:, -1, :], out.state

        def _decode(params, tok, state, pos):
            out = forward(cfg, params, tok, state=state, pos_offset=pos,
                          apply_head=apply_head,
                          trunk=trunk, trunk_isa=PHASE_ISA[DECODE])
            return out.logits[:, -1, :], out.state

        def _prefill_lanes_fn(params, tokens, states, offsets):
            # One batched trunk call over all active lanes: per-row
            # cache offsets (each lane appends at its own position),
            # then the rows split back into batch-1 partial states.
            stacked = _stack_lane_states(states)
            out = forward(cfg, params, tokens, state=stacked,
                          pos_offset=offsets, logits_mode="last",
                          apply_head=apply_head, trunk=trunk,
                          trunk_isa=PHASE_ISA[PREFILL])
            rows = [_slice_lane_state(out.state, i)
                    for i in range(len(states))]
            return out.logits[:, -1, :], rows

    if use_jit:
        _prefill = jax.jit(_prefill)
        _prefill_lanes_fn = jax.jit(_prefill_lanes_fn)
        _decode = functools.partial(jax.jit, donate_argnums=donate)(_decode)

    return _prefill, _prefill_lanes_fn, _decode


class ContinuousBatchingEngine:
    """Request-level engine: persistent decode batch + interleaved prefill.

    One :meth:`step` is one scheduler iteration:

    1. *(idle fast-forward)* with nothing admitted and nothing running, the
       clock jumps to the next arrival (open-loop traffic replay).
    2. *Prefill lane*: at most one prompt chunk (``prefill_chunk`` tokens,
       or the whole prompt) runs on a detached batch-1 state; on the last
       chunk the first token is sampled and the state is adopted into a
       free decode slot.
    3. *Decode lane*: one greedy step for the whole persistent batch;
       finished requests release their slots immediately (reused by the
       next admission — no barrier, late requests join mid-flight).

    ``cost_model`` (see :class:`~repro.serving.phases.PhaseCostModel`)
    replaces wall timing with deterministic virtual seconds; the jitted
    model still produces the real tokens.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_seq: int, prefill_chunk: Optional[int] = None,
                 prefill_lanes: int = 1,
                 sampler: Optional[Callable] = None, cost_model=None,
                 balanced_head=None, balanced_trunk=None, topology=None,
                 donate_state: bool = True):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.cost_model = cost_model
        if prefill_lanes < 1:
            raise ValueError("prefill_lanes must be >= 1")
        self.prefill_lanes = prefill_lanes
        # Optional hybrid kernel dispatch of the LM head (see
        # models.balanced_lm_head): the jitted trunk stops before the head
        # and the decode-step Fp32-Int4-Fp32 GEMV runs as balanced per-core
        # Pallas shards with per-phase ISA table keys.  ``balanced_trunk``
        # (a models.BalancedTrunk) extends the same loop to *every*
        # projection of the step — q/k/v/o and MLP up/gate/down run as
        # per-core shards through the io_callback bridge, eagerly when
        # the trunk disallows tracing, or (mode="compiled") as offset-
        # driven single-grid lowerings with zero host callbacks — under
        # (phase ISA x layer kind) table keys; its optional head replaces
        # ``balanced_head``.
        if balanced_head is not None and balanced_trunk is not None \
                and balanced_trunk.head is not None:
            raise ValueError(
                "pass either balanced_head or a balanced_trunk with a head, "
                "not both")
        self.balanced_trunk = balanced_trunk
        self.balanced_head = balanced_head
        # NUMA wiring: a balanced trunk bound to a repro.topology.
        # TopologyDispatcher is adopted automatically — its weights are
        # placed (column ranges pinned to the socket that streams them)
        # and the topology is exposed for telemetry.  Passing ``topology=``
        # explicitly asserts which machine the trunk must be balanced over.
        self.topology, self.placement = self._adopt_topology(
            balanced_trunk, topology)
        apply_head = (balanced_head is None
                      and (balanced_trunk is None
                           or balanced_trunk.head is None))
        # a replica committed to one device keeps its state and step inputs
        # there (None: JAX's default device)
        self.device = committed_device(params)
        with jax.default_device(self.device):
            self.manager = SlotCacheManager(cfg, max_slots, max_seq)
            # One prefill lane -> one partial state.  The fresh template is
            # allocated once and reused for every admission (_prefill never
            # donates its state argument, so the template stays intact).
            self._fresh_prefill_state = init_state(cfg, 1, max_seq)
        self.scheduler = IterationScheduler(prefill_chunk,
                                            prefill_lanes=prefill_lanes)
        # soft concurrency cap (<= max_slots): admission headroom only, so
        # a capacity event can shrink the effective batch without touching
        # allocated slot state or recompiling (shapes stay max_slots)
        self.slot_budget = max_slots
        self.now = 0.0
        self.finished: List[Request] = []
        self._running: List[Request] = []
        self._partial = None           # in-flight batch-1 prefill state
        self._partials = {}            # request_id -> state (multi-lane)
        self._next_id = 0
        # (B,) greedy rows by default; a sampler sees (B, V) logits.
        self._pick = sampler or (lambda lg: jnp.argmax(lg, -1))

        compiled = (balanced_trunk is not None
                    and getattr(balanced_trunk, "mode", None) == "compiled")
        self._compiled_trunk = compiled
        self._prefill, self._prefill_lanes, self._decode = step_programs(
            cfg, trunk=balanced_trunk, apply_head=apply_head,
            donate_state=donate_state)
        # Initial offset snapshot (compiled mode): planned from whatever
        # the ratio tables currently hold, refreshed after every step.
        self._offsets = (balanced_trunk.compiled_refresh() if compiled
                         else None)

    @staticmethod
    def _adopt_topology(trunk, topology):
        """Resolve the engine's machine topology from the balanced trunk's
        dispatcher (placing the trunk's weights NUMA-aware when the
        dispatcher is socket-local) and validate an explicit ``topology=``
        against it.  Returns (topology, TrunkPlacement) — (None, None)
        for flat dispatch."""
        from repro.topology import TopologyDispatcher, place_trunk

        disp = getattr(trunk, "dispatcher", None)
        if not isinstance(disp, TopologyDispatcher):
            if topology is not None:
                raise ValueError(
                    "topology= requires a balanced_trunk bound to a "
                    "repro.topology.TopologyDispatcher (the trunk decides "
                    "where its weights execute)")
            return None, None
        adopted = disp.topology
        if topology is not None:
            name = topology if isinstance(topology, str) else topology.name
            if (topology is not adopted and name != adopted.name):
                raise ValueError(
                    f"topology= names {name!r} but the balanced trunk is "
                    f"balanced over {adopted.name!r}")
        placement = place_trunk(trunk) if disp.socket_local else None
        return adopted, placement

    def _head(self, hidden: jax.Array, phase: str) -> jax.Array:
        """Apply the (possibly balanced) LM head to (B, d) hidden states."""
        if self._compiled_trunk and self.balanced_head is None:
            # Compiled trunk: its head (if any) already ran in-graph.
            return hidden
        if self.balanced_head is not None or (
                self.balanced_trunk is not None
                and self.balanced_trunk.head is not None):
            # The trunk step is dispatched asynchronously and its ordered
            # io_callbacks run on a jax runtime thread; the eager balanced
            # head launches its own shard programs from this thread.  On
            # the CPU client the two can starve each other out of
            # execution threads (the head's program holds one while
            # data-waiting on ``hidden``, the callback's inner shards
            # can't get one, the trunk can't finish without the callback)
            # — so drain the in-flight step before dispatching host work.
            jax.block_until_ready(hidden)
        if self.balanced_head is not None:
            return self.balanced_head(hidden, isa=PHASE_ISA[phase])
        if self.balanced_trunk is not None and self.balanced_trunk.head is not None:
            return self.balanced_trunk.apply_head(
                hidden, isa=PHASE_ISA[phase])
        return hidden  # jitted trunk already produced logits

    # ------------------------------------------------------------- intake --
    def submit(self, request: Request) -> int:
        """Queue a request; returns its engine-assigned id."""
        if request.prompt_len + 1 > self.max_seq:
            raise ValueError(
                f"prompt of {request.prompt_len} tokens cannot decode within "
                f"max_seq={self.max_seq}")
        request.request_id = self._next_id
        self._next_id += 1
        if request.host_queued is None:
            request.host_queued = time.perf_counter()
        self.scheduler.submit(request)
        return request.request_id

    def set_slot_budget(self, budget: int) -> int:
        """Re-plan the soft concurrency cap (a capacity event fired):
        admission stops above the budget while already-admitted requests
        run to completion — no slot state is evicted and no shape changes,
        so nothing retraces.  Clamped to ``[1, max_slots]`` (budget 0 with
        waiting work would wedge ``run_until_idle``; full drain is the
        dispatcher's ``set_active`` job).  Returns the applied budget."""
        self.slot_budget = int(np.clip(budget, 1, self.max_slots))
        return self.slot_budget

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work or bool(self._running)

    @property
    def n_running(self) -> int:
        return len(self._running)

    @property
    def n_waiting(self) -> int:
        return self.scheduler.n_waiting()

    @property
    def n_prefilling(self) -> int:
        return len(self.scheduler.lanes)

    @property
    def pending_prefill_tokens(self) -> int:
        """Prompt tokens queued ahead of a newly routed request (the
        dispatcher's prefill-pressure signal)."""
        pending = sum(r.prompt_len for r in self.scheduler.waiting)
        pending += sum(r.prompt_len - r.prefill_done
                       for r in self.scheduler.lanes)
        return pending

    @property
    def queue_depth(self) -> int:
        """Outstanding requests at every pre-finish stage (waiting +
        prefilling + running) — the admission controller's load probe."""
        return self.n_running + self.n_prefilling + self.n_waiting

    def outstanding(self) -> List[Request]:
        """Every request currently owned by the engine (queue, prefill
        lane(s), decode batch) — what a failing node must drain."""
        out = list(self.scheduler.waiting)
        out.extend(self.scheduler.lanes)
        out.extend(self._running)
        return out

    def steal_waiting(self) -> List[Request]:
        """Remove and return all still-WAITING requests (they never
        executed, so they can be resubmitted elsewhere verbatim — the
        retry-able half of a node drain; admitted requests have cache
        state here and must be aborted instead)."""
        out = list(self.scheduler.waiting)
        self.scheduler.waiting.clear()
        return out

    def poll_finished(self) -> List[Request]:
        """Drain and return requests finished since the last poll."""
        out, self.finished = self.finished, []
        return out

    def abort(self, request: Request) -> bool:
        """Cancel a request at any pre-finish stage (queue, prefill lane,
        or decode batch), releasing whatever it holds.  Returns False when
        it already finished."""
        if request.state is RequestState.FINISHED:
            return False
        man, sched = self.manager, self.scheduler
        if request.state is RequestState.WAITING:
            try:
                sched.waiting.remove(request)
            except ValueError:
                raise ValueError("request is not queued in this engine")
        elif request.state is RequestState.PREFILL:
            sched.remove_lane(request)  # raises when not prefilling here
            self._partial = None
            self._partials.pop(request.request_id, None)
            man.release(request.slot)
            request.slot = None
        elif request.state is RequestState.RUNNING:
            if request not in self._running:
                raise ValueError("request is not running in this engine")
            self._running.remove(request)
            man.release(request.slot)
            request.slot = None
        request.state = RequestState.FINISHED
        request.finish_reason = FinishReason.ABORTED
        request.finish_time = self.now
        self.finished.append(request)
        return True

    # -------------------------------------------------------------- step ---
    def step(self) -> IterationStats:
        """Run one scheduler iteration; returns what it did (the per-phase
        feedback record)."""
        with _ev.span("engine.step"), jax.default_device(self.device):
            return self._step()

    def _step(self) -> IterationStats:
        # Host spans (repro.core.events.span) mark each part of the step on
        # the wall clock, on both paths; with a cost model the engine also
        # emits its prefill/decode spans on the virtual clock (emit_span),
        # under the same names.
        st = IterationStats()
        man, sched = self.manager, self.scheduler

        with _ev.span("engine.schedule"):
            # Idle fast-forward: nothing to run until the next arrival.
            if (not self._running and not sched.lanes
                    and sched.waiting and not sched.n_waiting(self.now)):
                self.now = max(self.now, sched.waiting[0].arrival_time)
            # admission headroom: free slots, clamped by the soft slot
            # budget (a capacity event may have shrunk the sustainable
            # concurrency)
            budget_free = max(0, min(man.n_free,
                                     self.slot_budget - man.n_active))
            chunks = sched.next_prefill(self.now, budget_free)
            for c in chunks:
                if c.request.slot is None:  # newly admitted
                    self._admit(c.request)
        if chunks and self.prefill_lanes == 1:
            self._step_prefill(chunks[0], st)
        elif chunks:
            self._step_prefill_lanes(chunks, st)

        if self._running:
            with _ev.span("engine.decode", rows=len(self._running)):
                tok = jnp.asarray(man.last_token[:, None])
                pos = jnp.asarray(man.pos)
                t0 = time.perf_counter()
                if self._compiled_trunk:
                    logits, man.state, recs = self._decode(
                        self.params, tok, man.state, pos, self._offsets)
                else:
                    logits, man.state = self._decode(self.params, tok,
                                                     man.state, pos)
                with _ev.span("engine.decode.sync"):
                    next_tok = np.asarray(
                        self._pick(self._head(logits, DECODE))).reshape(-1)
            if self.cost_model is None:
                dt = time.perf_counter() - t0
            else:
                dt = self.cost_model.decode_seconds(
                    len(self._running), ctx=int(man.pos.max()))
            if self._compiled_trunk:
                self._feedback(recs)
            if self.cost_model is not None:
                _ev.emit_span(
                    "engine", "engine.decode", self.now, dt, cat="engine",
                    args=lambda: {"batch": len(self._running)})
            self.now += dt
            st.decode_tokens = len(self._running)
            st.decode_seconds = dt
            with _ev.span("engine.finish"):
                for req in list(self._running):
                    t = int(next_tok[req.slot])
                    req.generated.append(t)
                    man.last_token[req.slot] = t
                    man.pos[req.slot] += 1
                    self._maybe_finish(req, t, st)

        st.n_running = len(self._running)
        st.n_waiting = self.scheduler.n_waiting()
        st.now = self.now
        if self.cost_model is not None:
            _ev.emit_counter("queue", self.now,
                             lambda: {"depth": float(self.queue_depth)})
        return st

    def _admit(self, req: Request) -> None:
        """Reserve a decode slot for a request the prefill lane just took,
        and give it a fresh batch-1 prefill state."""
        req.slot = self.manager.allocate()
        req.state = RequestState.PREFILL
        req.admit_time = self.now
        req.host_admitted = time.perf_counter()
        if self.prefill_lanes == 1:
            self._partial = self._fresh_prefill_state
        else:
            self._partials[req.request_id] = self._fresh_prefill_state

    def _feedback(self, recs) -> None:
        """Compiled trunk, between steps: replay the step's cost tape into
        the ratio tables and refresh the offset snapshot."""
        with _ev.span("engine.feedback"):
            self._offsets = self.balanced_trunk.compiled_feedback(
                jax.device_get(recs))

    def _first_token(self, req: Request, tok: int, state, st) -> None:
        """A request's prefill ended with ``tok``: it joins the decode
        batch in its slot."""
        req.generated.append(tok)
        req.first_token_time = self.now
        req.host_first_token = time.perf_counter()
        self.manager.adopt(req.slot, state, req.prompt_len, tok)
        req.state = RequestState.RUNNING
        self._running.append(req)
        st.admitted.append(req.request_id)
        self._maybe_finish(req, tok, st)

    def _step_prefill(self, chunk, st: IterationStats) -> None:
        """One prompt piece of the single prefill lane, on its detached
        batch-1 state; the last piece samples the first token."""
        req = chunk.request
        with _ev.span("engine.prefill", tokens=chunk.length,
                      start=chunk.start):
            tokens = jnp.asarray(
                req.prompt[chunk.start:chunk.start + chunk.length][None, :])
            t0 = time.perf_counter()
            if self._compiled_trunk:
                logits, small, recs = self._prefill(
                    self.params, tokens, self._partial,
                    jnp.asarray(chunk.start, jnp.int32), self._offsets)
            else:
                logits, small = self._prefill(
                    self.params, tokens, self._partial,
                    jnp.asarray(chunk.start, jnp.int32))
            with _ev.span("engine.prefill.sync"):
                tok = None
                if chunk.is_last:
                    # head + sampling inside the timed window, matching the
                    # decode lane — with a balanced head the host-side GEMV
                    # is part of the step, so TTFT must include it
                    tok = int(np.asarray(
                        self._pick(self._head(logits, PREFILL))).reshape(-1)[0])
                if self.cost_model is None:
                    logits.block_until_ready()
        if self.cost_model is None:
            dt = time.perf_counter() - t0
        else:
            dt = self.cost_model.prefill_seconds(
                chunk.length, ctx=chunk.start + chunk.length)
        if self._compiled_trunk:
            self._feedback(recs)
        req.prefill_done += chunk.length
        req.prefill_pieces += 1
        self.scheduler.prefill_advanced(chunk)
        if self.cost_model is not None:
            _ev.emit_span("engine", "engine.prefill", self.now, dt,
                          cat="engine",
                          args=lambda: {"tokens": int(chunk.length)})
        self.now += dt
        st.prefill_tokens = chunk.length
        st.prefill_seconds = dt
        if chunk.is_last:
            self._partial = None
            self._first_token(req, tok, small, st)
        else:
            self._partial = small

    def _step_prefill_lanes(self, chunks, st: IterationStats) -> None:
        """Multi-lane prefill: all active lanes advance by one shared-length
        chunk through a *single* batched trunk call (per-row cache offsets),
        instead of one batch-1 call per prompt — the GEMM over B*L rows is
        what the balanced per-core split wants to see.  Token-identical to
        the batch-1 path: rows of a matmul are independent and each lane's
        cache rows are its own."""
        length = chunks[0].length
        with _ev.span("engine.prefill", tokens=length * len(chunks),
                      lanes=len(chunks)):
            tokens = jnp.asarray(np.stack(
                [np.asarray(c.request.prompt[c.start:c.start + length])
                 for c in chunks]))
            offsets = jnp.asarray(
                np.array([c.start for c in chunks], dtype=np.int32))
            states = [self._partials[c.request.request_id] for c in chunks]
            t0 = time.perf_counter()
            if self._compiled_trunk:
                logits, rows, recs = self._prefill_lanes(
                    self.params, tokens, states, offsets, self._offsets)
            else:
                logits, rows = self._prefill_lanes(self.params, tokens,
                                                   states, offsets)
            with _ev.span("engine.prefill.sync"):
                picked = None
                if any(c.is_last for c in chunks):
                    # head + sampling inside the timed window (TTFT)
                    picked = np.asarray(
                        self._pick(self._head(logits, PREFILL))).reshape(-1)
                if self.cost_model is None:
                    logits.block_until_ready()
        if self.cost_model is None:
            dt = time.perf_counter() - t0
        else:
            # one parallel region over all lanes' tokens: the batched call
            # is what splits across cores, so it is timed as one chunk
            dt = self.cost_model.prefill_seconds(
                length * len(chunks),
                ctx=max(c.start + length for c in chunks))
        if self._compiled_trunk:
            self._feedback(recs)
        if self.cost_model is not None:
            _ev.emit_span(
                "engine", "engine.prefill", self.now, dt, cat="engine",
                args=lambda: {"tokens": int(length * len(chunks)),
                              "lanes": len(chunks)})
        self.now += dt
        st.prefill_tokens = length * len(chunks)
        st.prefill_seconds = dt
        for i, c in enumerate(chunks):
            req = c.request
            req.prefill_done += length
            req.prefill_pieces += 1
            self.scheduler.prefill_advanced(c)
            if c.is_last:
                self._partials.pop(req.request_id, None)
                self._first_token(req, int(picked[i]), rows[i], st)
            else:
                self._partials[req.request_id] = rows[i]

    def _maybe_finish(self, req: Request, tok: int, st: IterationStats) -> None:
        stopped = req.stop_token is not None and tok == req.stop_token
        out_of_room = req.prompt_len + req.n_generated + 1 > self.max_seq
        if not (stopped or out_of_room
                or req.n_generated >= req.max_new_tokens):
            return
        req.finish_reason = (FinishReason.STOP if stopped
                             else FinishReason.LENGTH)
        req.finish_time = self.now
        req.state = RequestState.FINISHED
        self.manager.release(req.slot)
        req.slot = None
        self._running.remove(req)
        self.finished.append(req)
        st.finished.append(req.request_id)

    def run_until_idle(self, max_steps: Optional[int] = None) -> List[IterationStats]:
        """Step until every submitted request has finished."""
        stats = []
        while self.has_work:
            if max_steps is not None and len(stats) >= max_steps:
                break
            stats.append(self.step())
        return stats


class RoutedServer:
    """Seed-era batch API (paper Eq. 3 at the serving layer), now a thin
    compatibility wrapper over per-replica continuous-batching engines.

    The whole-batch contract is preserved — proportional split across
    replicas by the "serve_step" ratio entry, capacity clamp with overflow
    redistribution, per-replica measured (or injected) times fed back —
    but each replica's share executes through a
    :class:`ContinuousBatchingEngine` rather than a padded static batch.
    Note the engine admits through a single prefill lane, so a replica's
    ``c`` prompts prefill as ``c`` batch-1 calls instead of the seed's one
    batched call; on real hardware callers that want maximal prefill
    batching for a fixed, fully-arrived batch should keep using
    :meth:`ServeEngine.generate`.  Request-level callers should use
    :class:`~repro.serving.dispatch.InflightDispatcher` directly.
    """

    def __init__(self, engines: Sequence[ServeEngine],
                 sink: Optional[StatsSink] = None):
        self.engines = list(engines)
        self.runtime = DeviceRuntime(n_slices=len(engines), alpha=0.3)
        self.router = ReplicaRouter(self.runtime)
        # keep_stats=False: a serving process is long-lived; per-batch
        # telemetry goes to the sink, not an unbounded list.
        self.balancer = Balancer(self.router, sink=sink, keep_stats=False)
        self._cb_engines = None

    @property
    def _cb(self):
        """Per-replica continuous-batching engines, built on first use so a
        router-only RoutedServer does not allocate slot state up front."""
        if self._cb_engines is None:
            self._cb_engines = [
                ContinuousBatchingEngine(e.cfg, e.params,
                                         max_slots=e.batch_size,
                                         max_seq=e.max_seq)
                for e in self.engines
            ]
        return self._cb_engines

    @property
    def capacities(self) -> np.ndarray:
        return np.array([e.batch_size for e in self.engines], dtype=np.int64)

    def serve_batch(self, prompts: np.ndarray, n_steps: int,
                    times_override: Optional[np.ndarray] = None):
        """Split ``prompts`` across replicas ∝ current ratios; run; feed
        times back.  ``times_override`` lets tests/benchmarks inject
        simulated heterogeneous replica speeds."""
        if len(prompts) == 0:
            return (np.zeros((0, prompts.shape[1] + n_steps),
                             dtype=prompts.dtype),
                    np.zeros(len(self.engines), dtype=np.int64),
                    np.zeros(len(self.engines)))
        if n_steps == 0:
            # Seed contract: a 0-step round returns the prompts unchanged.
            # Nothing is decoded, so nothing is measured or fed back.
            counts = clamp_to_capacity(self.balancer.plan(len(prompts)).counts,
                                       self.capacities)
            return (np.array(prompts, copy=True), counts,
                    np.zeros(len(self.engines)))
        # The (B, s0 + n_steps) output contract needs cache room for every
        # step on whichever replica a request lands on; fail loudly up
        # front rather than silently returning a narrower array.
        s0 = prompts.shape[1]
        short = min(e.max_seq for e in self.engines)
        if s0 + n_steps > short:
            raise ValueError(
                f"prompt_len {s0} + n_steps {n_steps} exceeds replica "
                f"max_seq {short}; build engines with max_seq >= "
                f"prompt_len + n_steps")
        # The proportional split can exceed a fast replica's slot count;
        # clamp to capacity and hand the overflow to other replicas.
        planned = self.balancer.plan(len(prompts))
        counts = clamp_to_capacity(planned.counts, self.capacities)
        plan = Plan(counts=counts, key=planned.key)
        with self.balancer.balanced_region(plan=plan) as region:
            results, start = [], 0
            for i, (cb, c) in enumerate(zip(self._cb, counts)):
                if c == 0:
                    continue
                chunk = prompts[start:start + c]
                start += c
                reqs = [Request(prompt=p, max_new_tokens=n_steps)
                        for p in chunk]
                with region.timed(i):
                    for r in reqs:
                        r.arrival_time = cb.now
                        cb.submit(r)
                    cb.run_until_idle()
                cb.poll_finished()  # keep the long-lived engine bounded
                results.append(np.stack([r.tokens for r in reqs]))
            if times_override is not None:
                # Replicas that served nothing have no measurement this
                # round; keep their time at 0 so EMA updates and telemetry
                # skip them instead of learning from a phantom sample.
                override = np.asarray(times_override, dtype=np.float64)
                region.times[:] = np.where(counts > 0, override, 0.0)
        return np.concatenate(results, axis=0), counts, region.times
