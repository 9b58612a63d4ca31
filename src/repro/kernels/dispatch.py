"""Hybrid kernel dispatch: Balancer-planned per-core shards of real kernels.

This module closes the paper's loop at the layer it was written for.  The
Pallas kernels in this package execute as monolithic grids; the paper's
runtime instead splits every GEMM/GEMV along its N dimension into one
*contiguous* shard per core, sized by the per-ISA performance-ratio table
(Eq. 3), and feeds the measured shard times back into the table (Eq. 2):

    RatioTable["avx_vnni" | "membw"]  --Eq.3-->  per-core N shards
         ^                                           |
         |                                      worker pool runs the real
         +------------- Eq.2 + EMA <----------- Pallas shard (interpret on
                                                CPU, Mosaic on TPU)

:class:`HybridKernelDispatcher` owns that loop for any caller:

* ``dispatch(spec, total[, fn])`` — the low-level split/run/report cycle for
  an abstract kernel (used by the bandwidth benchmarks, ``fn=None`` runs the
  pure virtual-time model);
* ``q4_matmul(x, qw)`` / ``int8_gemm(a, w)`` — real sharded kernel
  execution: each worker's shard is a genuine ``pallas_call`` over that
  worker's weight rows, with per-shard block shapes chosen online by a
  :class:`~repro.core.tuner.KernelTuner`.

Primary-ISA keying follows the paper (kernels sharing a bottleneck share
ratios): compute-bound prefill GEMMs dispatch under ``"avx_vnni"``,
memory-bound decode GEMVs under ``"membw"``.  Balanced-trunk callers
additionally split the *table* key per layer kind — ``kernel_key(isa,
kind)`` produces ``"membw/attn_proj"``-style keys so every projection
family converges its own ratio vector while executing under its phase's
ISA.  Every region reports its bytes moved, so achieved-bandwidth
fractions fall out of the uniform :class:`~repro.runtime.RegionStats`
telemetry.

:func:`bridged_linear` is the jit bridge: the model trunk is a jitted
``lax``-free unrolled loop whose projections must reach these host-side
shard dispatchers.  Inside a trace it routes the call through an ordered
``io_callback`` (the sharded per-core Pallas calls stay usable from the
jitted decode step); outside a trace — or when the caller disallows the
callback — it falls back to direct eager shard-wise execution.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

# The jit bridge below runs ordered io_callbacks that themselves dispatch
# jitted Pallas shard programs.  jax's CPU client executes programs on a
# thread pool sized from the host CPU count; on a 1-2 CPU host the outer
# program can hold every execution thread while its callback waits on the
# nested shard program — a guaranteed deadlock.  Synchronous dispatch runs
# each program on the calling thread instead, which composes with nesting,
# so flip it where the pool is too small for the bridge to be safe.
if (os.cpu_count() or 1) <= 2:
    jax.config.update("jax_cpu_enable_async_dispatch", False)

from repro.core import events as _ev
from repro.core.hybrid_sim import SimulatedHybridCPU, make_machine
from repro.core.pool import SubTask, ThreadWorkerPool, VirtualWorkerPool
from repro.core.tuner import KernelTuner, shape_class
from repro.device import resolve_interpret
from repro.quant.q4 import BYTES_PER_ELEM, QuantizedLinear
from repro.runtime import (
    Balancer,
    EvenPolicy,
    KernelSpec,
    Plan,
    ProportionalPolicy,
    RatioTable,
    RegionStats,
    StatsSink,
)

# The package re-exports functions named like the kernel modules
# (`repro.kernels.int8_gemm` is the ops wrapper once __init__ has run), so
# the candidate tables must be imported from the submodules by full path.
from repro.kernels.int8_gemm import CANDIDATE_BLOCKS as _I8_CANDIDATES
from repro.kernels.q4_matmul import CANDIDATE_BLOCKS as _Q4_CANDIDATES
from . import ops

__all__ = ["HybridKernelDispatcher", "GEMM_ISA", "GEMV_ISA",
           "TRUNK_KINDS", "kernel_key", "bridged_linear",
           "bridged_linear_fused"]

GEMM_ISA = "avx_vnni"   # compute-bound prefill GEMM
GEMV_ISA = "membw"      # memory-bound decode GEMV

# Layer kinds of the balanced trunk: every decode-step projection family
# gets its own ratio-table key per ISA (q/k/v/o share "attn_proj"; the MLP
# up/gate projections share "mlp_up"; the down projection and the LM head
# stand alone).  Kinds sharing a bottleneck could share a table — keeping
# them separate lets the loop see per-family shape effects (granularity
# rounding at small N) without polluting the big-GEMV entries.
TRUNK_KINDS = ("attn_proj", "mlp_up", "mlp_down", "head")


def kernel_key(isa: str, kind: Optional[str] = None) -> str:
    """Ratio-table key for a trunk projection: ``"<isa>/<kind>"`` (or the
    bare ISA when no kind is given — the PR-3 balanced-head convention)."""
    return isa if kind is None else f"{isa}/{kind}"


def _bridge_run(layer, isa: str, key: Optional[str], x) -> np.ndarray:
    """Host half of :func:`bridged_linear`: one balanced shard dispatch."""
    return np.asarray(layer(jnp.asarray(x, jnp.float32), isa=isa, key=key),
                      dtype=np.float32)


def bridged_linear(layer, x: jax.Array, *, isa: str,
                   key: Optional[str] = None,
                   allow_callback: bool = True) -> jax.Array:
    """Apply a host-side balanced linear (``layer(x, isa=, key=)`` with an
    ``out_features`` attribute) from either side of a jit boundary.

    * Inside a trace: the call becomes an *ordered* ``io_callback`` — the
      jitted decode step stays one compiled program while every projection
      still runs as real per-core shards through the dispatcher's worker
      pools, with shard times fed back to the ratio table in program order.
    * Outside a trace (or with ``allow_callback=False``, the
      tracing-disallowed mode): direct eager shard-wise execution.

    Always computes in f32 (the dispatchers' accumulation dtype) and casts
    back to the caller's dtype.
    """
    if isinstance(x, jax.core.Tracer):
        if not allow_callback:
            raise RuntimeError(
                "balanced trunk was built with jit_bridge=False but its "
                "projections are being traced; run the forward eagerly "
                "(the engine skips jax.jit for such trunks)")
        out_shape = jax.ShapeDtypeStruct(x.shape[:-1] + (layer.out_features,),
                                         jnp.float32)
        fn = functools.partial(_bridge_run, layer, isa, key)
        out = io_callback(fn, out_shape, x, ordered=True)
    else:
        out = layer(x, isa=isa, key=key)
    return out.astype(x.dtype)


def _bridge_run_multi(layers, isa: str, keys, x) -> np.ndarray:
    """Host half of :func:`bridged_linear_fused`: one round trip runs every
    layer's balanced shard dispatch back to back (program order preserved,
    so ratio-table updates are identical to separate bridged calls)."""
    xj = jnp.asarray(x, jnp.float32)
    return np.concatenate(
        [np.asarray(layer(xj, isa=isa, key=key), dtype=np.float32)
         for layer, key in zip(layers, keys)], axis=-1)


def bridged_linear_fused(layers, x: jax.Array, *, isa: str, keys,
                         allow_callback: bool = True) -> tuple:
    """Apply several host-side balanced linears that share the same input
    through ONE jit-bridge round trip (the fused-q/k/v optimization: an
    attention layer's three input projections become a single ordered
    ``io_callback`` instead of three).

    Each layer still runs as its own balanced shard-dispatch region with
    its own table ``key`` — in the same order a sequence of
    :func:`bridged_linear` calls would — so outputs, shard times, and
    ratio-table updates are bit-identical to the per-matmul path; only the
    number of host round trips changes.  Returns one array per layer.
    """
    keys = list(keys)
    if len(keys) != len(layers):
        raise ValueError("need one table key per fused layer")
    if not isinstance(x, jax.core.Tracer):
        # eager: no bridge to amortize, so no concat/split round trip
        return tuple(layer(x, isa=isa, key=key).astype(x.dtype)
                     for layer, key in zip(layers, keys))
    if not allow_callback:
        raise RuntimeError(
            "balanced trunk was built with jit_bridge=False but its "
            "projections are being traced; run the forward eagerly "
            "(the engine skips jax.jit for such trunks)")
    widths = [layer.out_features for layer in layers]
    out_shape = jax.ShapeDtypeStruct(x.shape[:-1] + (sum(widths),),
                                     jnp.float32)
    fn = functools.partial(_bridge_run_multi, layers, isa, keys)
    cat = io_callback(fn, out_shape, x, ordered=True)
    outs, lo = [], 0
    for w in widths:
        outs.append(jax.lax.slice_in_dim(cat, lo, lo + w, axis=-1)
                    .astype(x.dtype))
        lo += w
    return tuple(outs)


class HybridKernelDispatcher:
    """Per-core balanced dispatch of kernel parallel regions.

    Construct via :meth:`virtual` (deterministic hybrid-CPU model, one
    :class:`VirtualWorkerPool` per ISA over a shared machine) or
    :meth:`threaded` (real OS threads with wall-clock shard times).  One
    dispatcher owns one :class:`RatioTable` keyed by primary ISA, one
    :class:`KernelTuner` for per-shard block shapes, and running
    bytes/busy-seconds accounting per ISA for achieved-bandwidth fractions.

    ``dynamic=False`` turns the dispatcher into the OpenMP-balanced static
    baseline (equal shards, no feedback) — same execution path, so dynamic
    vs. static comparisons isolate the paper's contribution.
    """

    def __init__(self, pool_factory: Callable[[str], object], n_workers: int,
                 *, machine: Optional[SimulatedHybridCPU] = None,
                 table: Optional[RatioTable] = None, alpha: float = 0.3,
                 tuner: Optional[KernelTuner] = None,
                 sink: Optional[StatsSink] = None, dynamic: bool = True,
                 interpret: Optional[bool] = None, keep_stats: bool = True):
        self.n_workers = n_workers
        self.machine = machine
        self.table = table or RatioTable(n_workers, alpha=alpha)
        if self.table.n_workers != n_workers:
            raise ValueError("table size does not match worker count")
        self.tuner = tuner or KernelTuner()
        self.sink = sink
        self.dynamic = dynamic
        # shard kernels interpret only on the CPU backend (repro.device)
        self.interpret = resolve_interpret(interpret)
        self.keep_stats = keep_stats
        self.stats: list = []
        self.last_stats: Optional[RegionStats] = None
        self._pool_factory = pool_factory
        self._pools: Dict[str, object] = {}
        self._balancers: Dict[tuple, Balancer] = {}
        # worker liveness the owner can flip directly (the replica-level
        # set_active idiom one level down); combined with the machine's
        # scheduled capacity events at plan time — see capacity_mask()
        self.active = np.ones(n_workers, dtype=bool)
        self._bytes: Dict[str, float] = {}
        self._busy: Dict[str, float] = {}
        # bytes/busy accounting is a read-modify-write on plain dicts;
        # shard reports may arrive from concurrent regions (threaded
        # pools, future async serving), so the accumulation is locked
        self._acct_lock = threading.Lock()

    # ------------------------------------------------------- constructors --
    @classmethod
    def virtual(cls, machine: SimulatedHybridCPU | str, *,
                execute: bool = False, seed: int = 0, **kwargs):
        """Dispatcher over the simulated hybrid CPU: shard times come from
        the core model; ``execute=True`` additionally runs the real kernel
        shards (correctness under virtual timing)."""
        if isinstance(machine, str):
            machine = make_machine(machine, seed=seed)
        if hasattr(machine, "sockets"):  # a MachineTopology, not a flat CPU
            raise ValueError(
                "multi-socket machines need repro.topology."
                "TopologyDispatcher (one flat dispatcher per bandwidth "
                "domain); HybridKernelDispatcher balances one socket")
        return cls(
            lambda isa: VirtualWorkerPool(machine, isa=isa, execute=execute),
            machine.n_cores, machine=machine, **kwargs)

    @classmethod
    def threaded(cls, n_workers: int, **kwargs):
        """Dispatcher over one persistent OS-thread pool (wall-clock shard
        times; the ISA only keys the ratio table)."""
        pool = ThreadWorkerPool(n_workers)
        return cls(lambda isa: pool, n_workers, **kwargs)

    def close(self) -> None:
        for pool in {id(p): p for p in self._pools.values()}.values():
            pool.close()

    # ------------------------------------------------------------ plumbing --
    def _pool(self, isa: str):
        if isa not in self._pools:
            self._pools[isa] = self._pool_factory(isa)
        return self._pools[isa]

    def set_active(self, i: int, active: bool = True) -> None:
        """Mark worker ``i`` parked (or returned).  Plans stop assigning
        to it; its ratio-table entry is untouched (zero-count workers are
        carried over by the ``units > 0`` rule), so it resumes at its last
        learned speed."""
        if not 0 <= i < self.n_workers:
            raise IndexError(f"worker {i} out of range")
        self.active[i] = bool(active)

    def capacity_mask(self, isa: str = GEMV_ISA) -> np.ndarray:
        """The plan-time active mask: explicit :meth:`set_active` state
        AND the machine's scheduled capacity events sampled at the ISA
        pool's clock (the time the next region will actually start) — so
        both eager dispatch and the compiled planner see fresh masks
        without extra wiring."""
        mask = self.active.copy()
        if self.machine is not None:
            pool = self._pools.get(isa)
            now = float(getattr(pool, "clock", 0.0)) if pool is not None else 0.0
            mask &= self.machine.active_mask(now)
        return mask

    def _balancer(self, spec: KernelSpec) -> Balancer:
        key = (spec.table_key, spec.granularity)
        if key not in self._balancers:
            if self.dynamic:
                policy = ProportionalPolicy(
                    self.table, key=spec.table_key,
                    granularity=spec.granularity,
                    active=lambda isa=spec.isa: self.capacity_mask(isa))
            else:
                # the static baseline stays capacity-blind on purpose:
                # that contrast is what bench_elastic measures
                policy = EvenPolicy(self.n_workers,
                                    granularity=spec.granularity)
            self._balancers[key] = Balancer(policy, sink=self.sink,
                                            keep_stats=False)
        return self._balancers[key]

    # ------------------------------------------------------------ dispatch --
    def dispatch(self, spec: KernelSpec, total: int,
                 fn: Optional[Callable[[int, int], None]] = None, *,
                 bytes_per_unit: float = 0.0, work_scale: float = 1.0,
                 update: bool = True,
                 plan: Optional[Plan] = None) -> RegionStats:
        """One balanced parallel region of ``total`` units along the
        kernel's split dimension: plan per-core contiguous shards, run them
        on the ISA's pool, feed shard times back.  ``fn(start, size)``
        executes one shard (``None``: purely modelled).  ``work_scale``
        inflates the modelled work per unit without changing the bytes
        accounting — the NUMA hook: a byte streamed from a remote socket
        costs ``cross_socket_penalty`` wall time but is still one byte.
        ``plan`` replays an externally realized split instead of planning
        afresh — the compiled-decode feedback path, where the per-core
        counts were fixed by the offset snapshot the device executed."""
        bal = self._balancer(spec)
        if plan is None:
            plan = bal.plan(total)
        elif int(np.asarray(plan.counts).sum()) != total:
            raise ValueError("replayed plan does not cover the region")
        work_per_unit = spec.work_per_unit * work_scale
        subtasks = [
            SubTask(worker=w, start=lo, size=hi - lo,
                    work=float(hi - lo) * work_per_unit, fn=fn)
            for w, (lo, hi) in enumerate(plan.ranges)
        ]
        pool = self._pool(spec.isa)
        tracing = _ev.TRACER is not None
        # virtual pools carry a deterministic clock; threaded pools don't,
        # so only virtual dispatch gets region spans (wall-clock spans
        # would break byte-identical traces)
        t0 = getattr(pool, "clock", None) if tracing else None
        times = pool.run(subtasks)
        moved = float(total) * bytes_per_unit
        st = bal.report(plan, times, update=update and self.dynamic,
                        label=f"{spec.name}@{spec.table_key}",
                        bytes_moved=moved)
        if moved > 0 and st.makespan > 0:
            self._account(spec.isa, moved, st.makespan)
        if t0 is not None:
            _ev.emit_span(
                f"dispatch:{spec.isa}", f"{spec.name}@{spec.table_key}",
                t0, pool.clock - t0, cat="dispatch",
                args=lambda: {"units": int(total),
                              "imbalance": round(st.imbalance, 4)})
            _ev.emit_counter(
                f"ratio:{spec.table_key}", pool.clock,
                lambda: {f"w{i}": round(float(r), 5) for i, r in
                         enumerate(self.table.ratios(spec.table_key))})
            _ev.emit_counter(
                f"capacity:{spec.isa}", pool.clock,
                lambda: {"active_workers": int(
                    self.capacity_mask(spec.isa).sum())})
            if moved > 0 and self.machine is not None:
                _ev.emit_counter(
                    f"bw:{spec.isa}", pool.clock,
                    lambda: {"achieved_bw_frac": round(
                        self.achieved_bandwidth_fraction(spec.isa), 5)})
        if self.keep_stats:
            self.stats.append(st)
        self.last_stats = st
        return st

    # ----------------------------------------------------------- telemetry --
    def _account(self, isa: str, moved: float, busy: float) -> None:
        """Accrue one region's bytes/busy under the accounting lock."""
        with self._acct_lock:
            if _ev.TRACER is not None:
                where = f"{type(self).__name__}._account"
                _ev.emit_acquire(self._acct_lock, where=where)
                _ev.emit_read(self, f"bytes[{isa}]", where=where)
                _ev.emit_write(self, f"bytes[{isa}]", where=where)
            self._bytes[isa] = self._bytes.get(isa, 0.0) + moved
            self._busy[isa] = self._busy.get(isa, 0.0) + busy
            if _ev.TRACER is not None:
                _ev.emit_release(self._acct_lock,
                                 where=f"{type(self).__name__}._account")

    def reset_bandwidth_accounting(self) -> None:
        """Zero the cumulative bytes/busy counters (steady-state windows:
        warm the ratio tables first, reset, then measure)."""
        self._bytes.clear()
        self._busy.clear()

    def achieved_bandwidth(self, isa: str = GEMV_ISA) -> float:
        """Bytes/s streamed by this dispatcher's ``isa`` regions so far
        (total bytes moved / total region makespan)."""
        busy = self._busy.get(isa, 0.0)
        if busy <= 0:
            return 0.0
        return self._bytes.get(isa, 0.0) / busy

    def achieved_bandwidth_fraction(self, isa: str = GEMV_ISA) -> float:
        """The paper's headline metric: achieved bandwidth as a fraction of
        the machine's streaming (MLC-analogue) bandwidth.  Requires a
        virtual machine (the denominator)."""
        if self.machine is None:
            raise ValueError("bandwidth fraction needs a simulated machine")
        return self.achieved_bandwidth(isa) / self.machine.socket_bandwidth

    # ------------------------------------------------------- real kernels --
    def _require_executing(self, isa: str) -> None:
        pool = self._pool(isa)
        if getattr(pool, "execute", True) is False:
            raise ValueError(
                "this dispatcher's virtual pool does not execute shard fns "
                "(construct with execute=True), so kernel outputs would be "
                "zeros; use dispatch() for purely modelled regions")

    def _select_blocks(self, kernel: str, m: int, size: int, k: int,
                       candidates) -> tuple:
        return self.tuner.select((kernel, shape_class(m, size, k)),
                                 candidates)

    def _shard_fn(self, kernel: str, m: int, k: int, candidates, blocks,
                  run_shard: Callable[[int, int, tuple], jnp.ndarray],
                  out: np.ndarray) -> Callable[[int, int], None]:
        """Wrap one shard execution: pick blocks (tuner unless pinned), run
        the real kernel over rows [start, start+size), time it for the
        tuner, write the rows into ``out``."""
        def fn(start: int, size: int) -> None:
            blk = blocks or self._select_blocks(kernel, m, size, k,
                                                candidates)
            t0 = time.perf_counter()
            y = run_shard(start, size, blk)
            y.block_until_ready()
            if blocks is None:
                self.tuner.report((kernel, shape_class(m, size, k)), blk,
                                  time.perf_counter() - t0)
            out[:, start:start + size] = np.asarray(y)
        return fn

    def q4_matmul(self, x, qw: QuantizedLinear, *, isa: str = GEMV_ISA,
                  key: Optional[str] = None,
                  blocks: Optional[tuple] = None, granularity: int = 8,
                  work_scale: float = 1.0, update: bool = True):
        """Fp32-Int4-Fp32 ``x (M,K) @ Q4_0 (N,K).T`` as balanced per-core
        N-row shards.  ``isa`` keys the ratio table ("membw" for decode
        GEMV, "avx_vnni" when the same kernel runs compute-bound prefill);
        ``key`` optionally refines the table key per layer kind (see
        :func:`kernel_key`); the virtual work model follows the
        bottleneck."""
        self._require_executing(isa)
        m, k = x.shape
        n = qw.out_features
        out = np.zeros((m, n), dtype=x.dtype)

        def run_shard(start, size, blk):
            shard = QuantizedLinear(qw.packed[start:start + size],
                                    qw.scales[start:start + size])
            return ops.q4_matmul(x, shard, blocks=blk,
                                 interpret=self.interpret)

        fn = self._shard_fn("q4_matmul", m, k, _Q4_CANDIDATES, blocks,
                            run_shard, out)
        bytes_per_row = k * BYTES_PER_ELEM
        work = bytes_per_row if isa == GEMV_ISA else 2.0 * m * k
        spec = KernelSpec("q4_matmul", isa=isa, granularity=granularity,
                          work_per_unit=work, key=key)
        self.dispatch(spec, n, fn, bytes_per_unit=bytes_per_row,
                      work_scale=work_scale, update=update)
        return jnp.asarray(out)

    def int8_gemm(self, a_u8, w_s8, *, isa: str = GEMM_ISA,
                  key: Optional[str] = None,
                  blocks: Optional[tuple] = None, granularity: int = 16,
                  work_scale: float = 1.0, update: bool = True):
        """u8 (M,K) x s8 (N,K) -> s32 (M,N) as balanced per-core N-row
        shards (the paper's VNNI prefill GEMM; s32 accumulation makes shard
        outputs bit-identical to the monolithic grid)."""
        self._require_executing(isa)
        m, k = a_u8.shape
        n = w_s8.shape[0]
        out = np.zeros((m, n), dtype=np.int32)

        def run_shard(start, size, blk):
            return ops.int8_gemm(a_u8, w_s8[start:start + size], blocks=blk,
                                 interpret=self.interpret)

        fn = self._shard_fn("int8_gemm", m, k, _I8_CANDIDATES, blocks,
                            run_shard, out)
        work = 2.0 * m * k if isa != GEMV_ISA else float(k)
        spec = KernelSpec("int8_gemm", isa=isa, granularity=granularity,
                          work_per_unit=work, key=key)
        self.dispatch(spec, n, fn, bytes_per_unit=float(k),
                      work_scale=work_scale, update=update)
        return jnp.asarray(out)

    def f32_matmul(self, x, w, *, isa: str = GEMV_ISA,
                   key: Optional[str] = None, granularity: int = 1,
                   work_scale: float = 1.0, update: bool = True):
        """f32 ``x (M,K) @ W (N,K).T`` as balanced per-core N-row shards of
        a plain host matmul — no quantization, no block constraints
        (``granularity=1``), so shard-wise output is exactly the monolithic
        product.  This is the trunk's precision-reference path: the bytes
        model streams the f32 weight rows (4K bytes each)."""
        self._require_executing(isa)
        x = np.asarray(x, dtype=np.float32)
        w = np.asarray(w, dtype=np.float32)
        m, k = x.shape
        n = w.shape[0]
        out = np.zeros((m, n), dtype=np.float32)

        def fn(start: int, size: int) -> None:
            out[:, start:start + size] = x @ w[start:start + size].T

        bytes_per_row = 4.0 * k
        work = bytes_per_row if isa == GEMV_ISA else 2.0 * m * k
        spec = KernelSpec("f32_matmul", isa=isa, granularity=granularity,
                          work_per_unit=work, key=key)
        self.dispatch(spec, n, fn, bytes_per_unit=bytes_per_row,
                      work_scale=work_scale, update=update)
        return jnp.asarray(out)
