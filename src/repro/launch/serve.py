"""Serving driver: request-level continuous batching with open-loop
(seeded Poisson) traffic, phase-aware ratio learning, and dynamic replica
routing.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --preset tiny \
      --replicas 2 --requests 8 --prompt-len 16 --steps 8 --rate 20

Modes:
* default — continuous batching: requests arrive open-loop and are routed
  to replicas by measured per-phase throughput; each replica interleaves
  chunked prefill with its running decode batch.  ``--machine`` drives a
  deterministic virtual clock from the paper's hybrid-CPU model (per-phase
  core dispatch); ``--machine wall`` uses real wall time, and is the
  default on an accelerator.  ``--preset full --layers N`` serves the
  published widths cut to ``N`` layers; with several devices, replica
  ``i`` lives on device ``i`` modulo their number.
* ``--legacy-batch`` — the seed-era whole-batch path (one
  ``RoutedServer.serve_batch`` round), kept for migration comparisons.
* ``--fleet`` — cluster-scale serving: a default heterogeneous fleet
  (NUMA flagship + NUMA desktop + flat box + throttled box) behind the
  recursive :class:`repro.fleet.FleetRouter`, driven by diurnal
  heavy-tailed traffic with a mid-run node failure window.
  ``--fleet-policy`` selects learned / round_robin / static routing and
  ``--fleet-admission`` adds the SLO-aware front door.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core import events as _ev
from repro.core.hybrid_sim import MACHINES
from repro.core.tuner import KernelTuner, TunerStore
from repro.device import device_info, enable_compile_cache
from repro.kernels import (
    GEMV_ISA,
    TRUNK_KINDS,
    HybridKernelDispatcher,
    kernel_key,
)
from repro.models import BalancedTrunk, balanced_lm_head, init_params
from repro.runtime import RatioStore, RatioTable
from repro.topology import TOPOLOGIES, TopologyDispatcher
from repro.serving import (
    DECODE,
    PREFILL,
    ContinuousBatchingEngine,
    HybridPhaseCost,
    InflightDispatcher,
    LatencyReport,
    RoutedServer,
    ServeEngine,
    poisson_requests,
)


def replica_slot_counts(batch: int, replicas: int) -> list:
    """Split a total concurrent-request budget across replicas: ``per``
    slots each plus the remainder spread over the first replicas (every
    replica gets at least one slot)."""
    if replicas < 1:
        raise ValueError("need at least one replica")
    base, rem = divmod(batch, replicas)
    return [max(1, base + (1 if i < rem else 0)) for i in range(replicas)]


def run_fleet_mode(args, cfg, params, max_seq: int, registry=None) -> int:
    """``--fleet``: the default heterogeneous 4-node cluster behind the
    recursive FleetRouter, under diurnal heavy-tailed traffic with a
    mid-run failure window on the largest node."""
    from repro.fleet import (
        AdmissionController,
        Cluster,
        FleetRouter,
        NodeSpec,
        failure_window,
        fleet_requests,
    )

    specs = (
        NodeSpec("big", "dual-125h", max_slots=args.batch, prefill_lanes=2),
        NodeSpec("mid", "2s-12900k", max_slots=args.batch, prefill_lanes=2),
        NodeSpec("flat", "ultra-125h", max_slots=args.batch),
        NodeSpec("slow", "ultra-125h", max_slots=args.batch, throttle=3.0),
    )
    cluster = Cluster.build(specs, cfg, params, max_seq=max_seq,
                            seed=args.seed)
    admission = None
    if args.fleet_admission:
        admission = AdmissionController(queue_cap=6 * len(specs),
                                        degrade_depth=3 * len(specs))
    # --ratios warm-starts/persists the *node-level* fleet table here
    # (same store format the replica path uses): a restarted router skips
    # the cold-start rounds where every node looks identical.
    table = RatioTable(len(specs), alpha=0.3)
    store = RatioStore(args.ratios) if args.ratios else None
    if store is not None and store.load_into(table):
        print(f"[serve] warm-started fleet node ratios from {args.ratios}")
    router = FleetRouter(cluster, policy=args.fleet_policy, table=table,
                         slo_ttft=2.0, slo_tpot=0.25, admission=admission)
    requests = fleet_requests(
        args.requests, base_rate=args.rate, vocab_size=cfg.vocab_size,
        prompt_len=(4, args.prompt_len), max_new_tokens=args.steps,
        seed=args.seed)
    # fail the flagship a quarter of the way through the expected span,
    # bring it back past the halfway crest
    span = args.requests / args.rate
    events = failure_window("big", fail_at=0.25 * span,
                            recover_at=0.6 * span)
    t_wall = time.perf_counter()
    done = router.run(requests, events)
    report = LatencyReport.from_requests(
        done, slo_ttft=2.0, slo_tpot=0.25,
        wall_duration=time.perf_counter() - t_wall)
    if registry is not None:
        report.publish(registry)
    names = [n.name for n in cluster.nodes]
    print(f"[serve] fleet {names} policy={args.fleet_policy} "
          f"routed={router.routed.tolist()} requeued={router.n_requeued}")
    for line in report.lines():
        print(line)
    print(f"[serve] node prefill ratios: "
          f"{np.round(router.table.ratios(PREFILL), 3).tolist()}")
    print(f"[serve] node decode  ratios: "
          f"{np.round(router.table.ratios(DECODE), 3).tolist()}")
    st = router.last_stats.get(DECODE)
    if st is not None:
        print(f"[serve] recursive decode stats: {len(st.children)} node "
              f"domains under the fleet table")
    if store is not None:
        store.save(router.table)
        print(f"[serve] saved fleet node ratios to {args.ratios}")
    return 0


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and reconcile the serve options (``--topology`` implies a
    balanced trunk and brings its own virtual clock)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--preset", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep this many layers of the preset (a depth cut; "
                         "every width stays as the preset has it)")
    ap.add_argument("--batch", type=int, default=4,
                    help="total concurrent-request slots across replicas")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="KV-cache positions per slot (default: prompt "
                         "length + steps + 8)")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop Poisson arrival rate, req/s (0: all at t=0)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens prefilled per iteration (0: one-shot)")
    ap.add_argument("--machine", default=None,
                    choices=sorted(MACHINES) + ["wall"],
                    help="virtual hybrid-CPU clock, or 'wall' for real "
                         "time (default: ultra-125h on the CPU backend, "
                         "wall on an accelerator)")
    ap.add_argument("--topology", default=None,
                    choices=sorted(TOPOLOGIES) + sorted(MACHINES),
                    help="serve on a NUMA topology: the balanced trunk "
                         "dispatches socket-local (two-level ratio split, "
                         "NUMA-placed weights) and the virtual clock runs "
                         "on the flattened machine; implies "
                         "--balanced-trunk (flat machine names are the "
                         "1-socket special case)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights, the traffic and the "
                         "virtual clocks")
    ap.add_argument("--ratios", default=None,
                    help="JSON path to warm-start/persist replica ratios")
    ap.add_argument("--legacy-batch", action="store_true",
                    help="run the seed-era whole-batch serve_batch path")
    ap.add_argument("--fleet", action="store_true",
                    help="serve on the default heterogeneous 4-node fleet "
                         "through the recursive FleetRouter (diurnal "
                         "traffic + mid-run failure window)")
    ap.add_argument("--fleet-policy", default="learned",
                    choices=["learned", "round_robin", "static"],
                    help="fleet routing policy (with --fleet)")
    ap.add_argument("--fleet-admission", action="store_true",
                    help="enable SLO-aware admission control (queue cap, "
                         "graceful degradation) in front of the fleet")
    ap.add_argument("--balanced-head", action="store_true",
                    help="run the LM head as balanced per-core Q4 Pallas "
                         "shards (hybrid kernel dispatch) instead of inside "
                         "the jitted trunk")
    ap.add_argument("--balanced-trunk", action="store_true",
                    help="run EVERY trunk projection (q/k/v/o, MLP "
                         "up/gate/down, head) as balanced per-core shards "
                         "through the io_callback bridge, with per-phase x "
                         "per-layer-kind ratio keys")
    ap.add_argument("--trunk-quant", choices=["q4", "int8", "fp32"],
                    default="q4",
                    help="balanced-trunk weight path: Q4_0 Pallas GEMV, "
                         "dynamic-u8xs8 INT8 GEMM, or shard-exact fp32")
    ap.add_argument("--trunk-mode", choices=["bridge", "compiled"],
                    default="bridge",
                    help="balanced-trunk execution: io_callback bridge into "
                         "the host worker pools, or compiled single-grid "
                         "Pallas projections with zero host callbacks (the "
                         "only mode that runs on an accelerator)")
    ap.add_argument("--tuner-cache", default=None,
                    help="JSON path to warm-start/persist the kernel "
                         "tuner's block-shape tables (shared across "
                         "replicas, like --ratios for ratio tables)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "run: spans on the virtual clock at every "
                         "balancing level plus ratio / bandwidth / "
                         "capacity counter tracks")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write run metrics (TTFT/TPOT histograms, "
                         "goodput): Prometheus text exposition, or a JSON "
                         "dump when PATH ends in .json")
    ap.add_argument("--flight-recorder", default=None, metavar="PATH",
                    help="record balancer decisions (ratio reports, offset "
                         "refreshes, capacity/admission events) in a "
                         "bounded ring dumped to PATH; auto-dumps on SLO "
                         "burn or contract trip")
    args = ap.parse_args(argv)
    if args.topology:
        if args.balanced_head:
            raise SystemExit("--topology dispatches the whole trunk; "
                             "drop --balanced-head")
        if args.machine is not None:
            raise SystemExit(
                "--topology provides the virtual clock (the topology's "
                "flattened machine); drop --machine")
        args.balanced_trunk = True
    if args.machine is None:
        # a topology brings its own virtual clock; otherwise a virtual clock
        # on a chip would report simulated seconds as latency
        virtual = args.topology or jax.default_backend() == "cpu"
        args.machine = "ultra-125h" if virtual else "wall"
    if args.balanced_head and args.balanced_trunk:
        raise SystemExit("--balanced-trunk already includes the head; "
                         "drop --balanced-head")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    dev = device_info()
    print(f"[serve] device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    print(f"[serve] compile cache: {enable_compile_cache()}")

    cfg, params, max_seq = build_model(args)
    slot_counts = replica_slot_counts(args.batch, args.replicas)

    # observability: install the tracer / flight recorder before any mode
    # runs, write the artifacts after it returns (or raises)
    tracer = recorder = registry = None
    prev_tracer = prev_recorder = None
    if args.trace:
        from repro.obs import SpanTracer
        tracer = SpanTracer()
        prev_tracer = _ev.install(tracer)
    if args.flight_recorder:
        from repro.obs import FlightRecorder
        recorder = FlightRecorder(
            path=args.flight_recorder,
            slo_ttft=2.0 if args.fleet else None,
            slo_tpot=0.25 if args.fleet else None)
        prev_recorder = _ev.install_recorder(recorder)
    if args.metrics:
        from repro.obs import MetricsRegistry
        registry = MetricsRegistry()
    try:
        return run_mode(args, cfg, params, max_seq, slot_counts, registry)
    finally:
        if tracer is not None:
            _ev.install(prev_tracer)
            tracer.write(args.trace)
            print(f"[serve] wrote trace to {args.trace} "
                  f"({tracer.n_spans} spans, {tracer.n_counters} counter "
                  f"samples, {tracer.n_instants} instants)")
        if recorder is not None:
            _ev.install_recorder(prev_recorder)
            if recorder.last_dump is None:
                recorder.trip("exit")
            print(f"[serve] flight recorder: {len(recorder.records())} "
                  f"records, {len(recorder.trips)} trip(s) -> "
                  f"{args.flight_recorder}")
        if registry is not None:
            if args.metrics.endswith(".json"):
                registry.write_json(args.metrics)
            else:
                with open(args.metrics, "w", encoding="utf-8") as fh:
                    fh.write(registry.prometheus_text())
            print(f"[serve] wrote metrics to {args.metrics}")


def model_config(args):
    """The preset's config, cut to ``--layers``."""
    cfg = (get_config(args.arch) if args.preset == "full"
           else reduced_config(args.arch))
    if cfg.embed_input:
        raise SystemExit("use examples/ for stub-frontend archs")
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    return cfg


def build_model(args) -> tuple:
    """``(cfg, params, max_seq)`` as the options say, with random weights
    made from ``--seed``."""
    cfg = model_config(args)
    params = init_params(cfg, jax.random.key(args.seed))
    max_seq = args.max_seq or args.prompt_len + args.steps + 8
    return cfg, params, max_seq


def make_requests(args, cfg) -> list:
    """The seeded open-loop traffic the options describe."""
    return poisson_requests(
        args.requests, rate=args.rate, vocab_size=cfg.vocab_size,
        prompt_len=args.prompt_len, max_new_tokens=args.steps,
        seed=args.seed)


def build_replicas(args, cfg, params, max_seq, slot_counts, *, tuner=None,
                   sampler=None) -> tuple:
    """One :class:`ContinuousBatchingEngine` per entry of ``slot_counts``,
    wired as the options say (clock, balanced head or trunk, dispatcher);
    returns ``(engines, kernel dispatchers)``.  With several devices,
    replica ``i`` is committed to device ``i % n_devices`` (params, slot
    cache and step inputs); ``sampler`` is the engines' logits hook."""
    devices = jax.devices()
    chunk = args.prefill_chunk if args.prefill_chunk > 0 else None
    engines, dispatchers = [], []
    for i, n_slots in enumerate(slot_counts):
        p = (params if len(devices) == 1
             else jax.device_put(params, devices[i % len(devices)]))
        clock = args.topology or args.machine
        cost = (None if args.machine == "wall"
                else HybridPhaseCost(clock, seed=args.seed + i))
        head, trunk = None, None
        if args.balanced_head or args.balanced_trunk:
            if args.topology:
                disp = TopologyDispatcher(args.topology,
                                          seed=args.seed + i, execute=True,
                                          keep_stats=False, tuner=tuner)
            elif args.machine == "wall":
                disp = HybridKernelDispatcher.threaded(4, keep_stats=False,
                                                       tuner=tuner)
            else:
                disp = HybridKernelDispatcher.virtual(
                    args.machine, seed=args.seed + i, execute=True,
                    keep_stats=False, tuner=tuner)
            dispatchers.append(disp)
            if args.balanced_trunk:
                trunk = BalancedTrunk.from_params(cfg, p, disp,
                                                  quant=args.trunk_quant,
                                                  mode=args.trunk_mode)
            else:
                head = balanced_lm_head(cfg, p, disp)
        engines.append(ContinuousBatchingEngine(
            cfg, p, max_slots=n_slots, max_seq=max_seq,
            prefill_chunk=chunk, sampler=sampler, cost_model=cost,
            balanced_head=head, balanced_trunk=trunk))
    return engines, dispatchers


def serve_requests(args, engines, requests, *, table=None) -> tuple:
    """Serve ``requests`` open loop across ``engines`` behind one
    :class:`InflightDispatcher`; returns ``(dispatcher, requests routed per
    replica, LatencyReport)``."""
    disp = InflightDispatcher(engines, table=table)
    routed = np.zeros(len(engines), dtype=np.int64)
    t_wall = time.perf_counter()
    for r in requests:
        # Let in-flight work progress up to this arrival so per-phase
        # throughput feedback from earlier requests steers the routing of
        # later ones (open loop: arrivals never wait on service).
        while disp.has_work and disp.now < r.arrival_time:
            disp.step()
        i, _ = disp.submit(r)
        routed[i] += 1
    disp.run_until_idle()
    report = LatencyReport.from_requests(
        requests, clock="virtual" if args.machine != "wall" else "wall",
        wall_duration=time.perf_counter() - t_wall)
    return disp, routed, report


def run_mode(args, cfg, params, max_seq, slot_counts, registry=None) -> int:
    """Dispatch to the selected serving mode (fleet / legacy / default)."""
    if args.fleet:
        if (args.legacy_batch or args.balanced_head or args.balanced_trunk
                or args.topology):
            raise SystemExit("--fleet is a standalone mode: the fleet owns "
                             "its topologies and cost models")
        return run_fleet_mode(args, cfg, params, max_seq, registry)

    if args.legacy_batch:
        rng = np.random.default_rng(args.seed)
        prompts = rng.integers(0, cfg.vocab_size,
                               size=(args.batch, args.prompt_len),
                               dtype=np.int32)
        engines = [ServeEngine(cfg, params, batch_size=n, max_seq=max_seq)
                   for n in slot_counts]
        srv = RoutedServer(engines)
        out, counts, times = srv.serve_batch(prompts, args.steps)
        print(f"[serve] legacy routed counts={counts.tolist()} "
              f"times={times.round(3).tolist()}")
        print(f"[serve] generated shape={out.shape}")
        return 0

    # One kernel tuner shared by every replica dispatcher so a single
    # --tuner-cache file accumulates all block-shape measurements.
    tuner = KernelTuner()
    tuner_store = TunerStore(args.tuner_cache) if args.tuner_cache else None
    if tuner_store is not None and tuner_store.load_into(tuner):
        print(f"[serve] warm-started kernel tuner from {args.tuner_cache}")
    engines, dispatchers = build_replicas(args, cfg, params, max_seq,
                                          slot_counts, tuner=tuner)

    table = RatioTable(args.replicas, alpha=0.3)
    store = RatioStore(args.ratios) if args.ratios else None
    if store is not None and store.load_into(table):
        print(f"[serve] warm-started replica ratios from {args.ratios}")
    requests = make_requests(args, cfg)
    disp, routed, report = serve_requests(args, engines, requests,
                                          table=table)
    if registry is not None:
        report.publish(registry)
    print(f"[serve] {args.replicas} replica(s), slots={slot_counts}, "
          f"routed={routed.tolist()} ({report.clock} clock)")
    for line in report.lines():
        print(line)
    print(f"[serve] replica prefill ratios: "
          f"{np.round(disp.table.ratios(PREFILL), 3).tolist()}")
    print(f"[serve] replica decode  ratios: "
          f"{np.round(disp.table.ratios(DECODE), 3).tolist()}")
    if args.machine != "wall":
        core = engines[0].cost_model.table
        print(f"[serve] core ratio spread (replica 0): "
              f"prefill={core.ratios(PREFILL).max() / core.ratios(PREFILL).min():.2f}x "
              f"decode={core.ratios(DECODE).max() / core.ratios(DECODE).min():.2f}x")
        print(f"[serve] decode achieved-bandwidth fraction (replica 0): "
              f"{engines[0].cost_model.achieved_bandwidth_fraction():.2f}")
    if args.balanced_head and args.machine != "wall":
        d0 = dispatchers[0]
        kt = d0.table.ratios(GEMV_ISA)
        print(f"[serve] balanced-head kernel table (replica 0): "
              f"membw spread={kt.max() / kt.min():.2f}x "
              f"achieved_bw_frac={d0.achieved_bandwidth_fraction():.2f}")
    if args.topology:
        d0 = dispatchers[0]
        print(f"[serve] topology {args.topology}: "
              f"{d0.topology.n_sockets} socket(s), "
              f"aggregate {d0.topology.aggregate_bandwidth / 1e9:.1f} GB/s")
        if engines[0].placement is not None:
            for line in engines[0].placement.lines():
                print(line)
        for kind in TRUNK_KINDS:
            key = kernel_key(GEMV_ISA, kind)
            if key in d0.table.keys():
                print(f"[serve] socket split {key}: "
                      f"{np.round(d0.socket_ratios(key), 3).tolist()}")
        fracs = [d0.achieved_bandwidth_fraction(socket=s)
                 for s in range(d0.topology.n_sockets)]
        print(f"[serve] per-socket decode achieved_bw_frac (replica 0): "
              f"{[round(f, 2) for f in fracs]}")
        print(f"[serve] aggregate decode achieved_bw_frac (replica 0): "
              f"{d0.achieved_bandwidth_fraction():.2f}")
    elif args.balanced_trunk and args.machine != "wall":
        d0 = dispatchers[0]
        for kind in TRUNK_KINDS:
            key = kernel_key(GEMV_ISA, kind)
            if key in d0.table.keys():
                kt = d0.table.ratios(key)
                print(f"[serve] trunk {key} spread: "
                      f"{kt.max() / kt.min():.2f}x")
        print(f"[serve] trunk decode achieved_bw_frac (replica 0): "
              f"{d0.achieved_bandwidth_fraction():.2f}")
    sample = requests[0].tokens
    print("[serve] sample:", sample[-min(16, args.steps):].tolist())
    if store is not None:
        store.save(table)
        print(f"[serve] saved replica ratios to {args.ratios}")
    if tuner_store is not None:
        tuner_store.save(tuner)
        print(f"[serve] saved kernel tuner tables to {args.tuner_cache}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
