"""The `simon` CLI: apply / server / version / gen-doc.

Mirrors the reference's cobra command tree (/root/reference/cmd/): same
subcommands, flags (including shorthands), and the `LogLevel` env knob
(cmd/simon/simon.go:46-66).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import List, Optional, Tuple

from .. import __version__
from ..core import constants as C

COMMIT_ID = ""  # stamped by packaging, like the reference's ldflags (Makefile:9-10)

_LOG_LEVELS = {
    "Panic": logging.CRITICAL,
    "Fatal": logging.CRITICAL,
    "Error": logging.ERROR,
    "Warn": logging.WARNING,
    "Info": logging.INFO,
    "Debug": logging.DEBUG,
    "Trace": logging.DEBUG,
}


def _init_logging() -> None:
    level = _LOG_LEVELS.get(os.environ.get(C.EnvLogLevel, ""), logging.INFO)
    logging.basicConfig(level=level, format="%(levelname)s %(message)s")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="simon",
        description=(
            "Simon is a simulator, which will simulate a cluster and simulate "
            "workload scheduling."
        ),
    )
    sub = parser.add_subparsers(dest="command", metavar="command")

    p_apply = sub.add_parser(
        "apply",
        help="Make a reasonable cluster capacity planning based on application "
             "resource requirements",
    )
    p_apply.add_argument(
        "-f", "--simon-config", required=True,
        help="path of the simon config file (simon/v1alpha1 Config)",
    )
    p_apply.add_argument(
        "--default-scheduler-config", default="",
        help="path to JSON or YAML file containing scheduler configuration.",
    )
    p_apply.add_argument("--output-file", default="", help="save report to output file.")
    p_apply.add_argument(
        "--profile", default="", metavar="DIR",
        help="write a jax.profiler device trace of the run to DIR "
             "(view with TensorBoard); the device-side analog of the "
             "reference's pprof endpoints.")
    p_apply.add_argument(
        "--use-greed", action="store_true", help="use greedy algorithm when queue pods"
    )
    p_apply.add_argument(
        "-i", "--interactive", action="store_true", help="interactive mode"
    )
    p_apply.add_argument(
        "--extended-resources", default="",
        help="show extended resources when reporting, comma-separated "
             "(e.g. open-local,gpu)",
    )
    p_apply.add_argument(
        "--placement-dump", default="",
        help="write a JSON placement dump for the parity tool",
    )
    p_apply.add_argument(
        "--trace-out", default="", metavar="FILE.json",
        help="write a Chrome trace-event JSON of the run's host spans "
             "(perfetto-loadable; includes the metrics snapshot as metadata)")
    p_apply.add_argument(
        "--metrics-out", default="", metavar="FILE.json",
        help="write the metrics-registry snapshot of the run as JSON "
             "(render later with `simon metrics FILE.json`)")
    p_apply.add_argument(
        "--deadline", type=float, default=0.0, metavar="SECONDS",
        help="wall-clock budget for the whole run; the capacity search and "
             "every simulation slice the remaining budget and the run fails "
             "cleanly when it expires (0 = unbounded)")
    p_apply.add_argument(
        "--resume-journal", default="", metavar="FILE.jsonl",
        help="crash-consistent capacity-search journal: probe verdicts are "
             "fsync'd to FILE as the search runs, and a re-run of the SAME "
             "search (options digest must match) resumes from it, skipping "
             "completed probes instead of recomputing an hour of search "
             "after a crash/SIGKILL")
    p_apply.add_argument(
        "--fault-plan", default="", metavar="SPEC",
        help="activate a deterministic fault-injection plan for the run: a "
             "JSON file, inline JSON, 'seed=N', or "
             "'site=S,attempt=K,error=E[;...]' (sites: see "
             "open_simulator_tpu.resilience.SITES). Testing/CI only.")
    p_apply.add_argument(
        "--xray", action="store_true",
        help="record per-pod scheduling decision records (simonxray flight "
             "recorder): segment attribution, per-plugin filter masks and "
             "score breakdowns, preemption victim chains. Query afterwards "
             "with `simon explain POD`.")
    p_apply.add_argument(
        "--xray-out", default="simon-xray", metavar="PREFIX",
        help="trace file prefix for --xray (writes PREFIX.jsonl + "
             "PREFIX.npz; default: simon-xray)")

    p_metrics = sub.add_parser(
        "metrics", help="Render a saved metrics snapshot (--metrics-out / "
                        "--trace-out file) as Prometheus text, or diff two "
                        "snapshots with --diff")
    p_metrics.add_argument(
        "snapshot", nargs="+",
        help="snapshot or trace JSON file (two files with --diff)")
    p_metrics.add_argument(
        "--diff", action="store_true",
        help="render per-metric deltas between TWO dumps (A B: changes from "
             "A to B), flagging counter regressions — compile-cache misses, "
             "retries, rollbacks and friends that grew, and counters that "
             "went backwards (different-process baselines)")
    p_metrics.add_argument(
        "--fail-on-regression", action="store_true",
        help="with --diff: exit 1 when any regression-direction counter grew")

    p_explain = sub.add_parser(
        "explain", help="Explain one pod's scheduling decision from a "
                        "simonxray trace (apply --xray): the kube-parity "
                        "event string, per-plugin filter rejections, and the "
                        "score breakdown vs the runner-up nodes")
    p_explain.add_argument("pod", nargs="?", default="",
                           help="pod to explain ('namespace/name', or a bare "
                                "name when unambiguous)")
    p_explain.add_argument(
        "--trace", default="simon-xray", metavar="PREFIX",
        help="xray trace prefix or .jsonl path (default: simon-xray)")
    p_explain.add_argument(
        "--unscheduled", action="store_true",
        help="list every unscheduled pod in the trace with its reason "
             "string instead of explaining one pod")
    p_explain.add_argument("--json", action="store_true",
                           help="emit the raw decision record as JSON")

    p_parity = sub.add_parser(
        "parity", help="Compute the placement match-rate between two dumps "
                       "written by `apply --placement-dump`")
    p_parity.add_argument("dump_a")
    p_parity.add_argument("dump_b")
    p_parity.add_argument("--threshold", type=float, default=0.99,
                          help="exit nonzero below this rate")
    p_parity.add_argument("-v", "--verbose", action="store_true",
                          help="list disagreeing placements")

    p_lint = sub.add_parser(
        "lint", add_help=False,
        help="Run simonlint, the JAX/TPU-hazard static analyzer, over the "
             "given paths (default: the open_simulator_tpu package)")
    p_lint.add_argument("lint_args", nargs=argparse.REMAINDER)

    p_audit = sub.add_parser(
        "audit", add_help=False,
        help="Run simonaudit: lower every registered hot kernel on CPU and "
             "diff its compile-time dispatch certificate (collective census, "
             "donation, host-callback escapes, recompile digest) against the "
             "goldens in tests/golden/audit/ (--check / --update)")
    p_audit.add_argument("audit_args", nargs=argparse.REMAINDER)

    p_server = sub.add_parser("server", help="Start a HTTP server that simulates "
                                             "deploy/scale requests against a live cluster")
    p_server.add_argument("--kubeconfig", default="", help="path of the kubeconfig file")
    p_server.add_argument("--master", default="", help="URL of the kube-apiserver")
    p_server.add_argument("--port", type=int, default=8080, help="listen port")
    p_server.add_argument(
        "--grpc-port", type=int, default=0, metavar="PORT",
        help="also serve the gRPC bridge (server/proto/simon.proto) on PORT "
             "(0 = disabled)")
    p_server.add_argument(
        "--drain-deadline", type=float, default=None, metavar="SECONDS",
        help="graceful-drain budget on SIGTERM: stop accepting (503), let "
             "in-flight requests finish up to SECONDS, then exit "
             "(default 25)")
    p_server.add_argument(
        "--debug-faults", action="store_true",
        help="enable the POST /debug/fault-plan injection endpoint "
             "(testing/CI only; never enable on a production server)")
    p_server.add_argument(
        "--xray", action="store_true",
        help="keep in-memory scheduling decision records and serve them on "
             "GET /explain/<pod> (+ the unscheduled summary on /debug/vars)")

    p_serve = sub.add_parser(
        "serve", help="Start the resident what-if server (simonserve): a "
                      "persistent device-resident cluster image with delta "
                      "ingest and micro-batched /v1/whatif serving")
    p_serve.add_argument("--kubeconfig", default="", help="path of the kubeconfig file")
    p_serve.add_argument("--master", default="", help="URL of the kube-apiserver")
    p_serve.add_argument("--port", type=int, default=8080, help="listen port")
    p_serve.add_argument(
        "--grpc-port", type=int, default=0, metavar="PORT",
        help="also serve the gRPC bridge (incl. the WhatIf RPC) on PORT "
             "(0 = disabled)")
    p_serve.add_argument(
        "--window-ms", type=float, default=2.0, metavar="MS",
        help="micro-batching window: concurrent what-if requests arriving "
             "within MS coalesce onto one fan-out dispatch (default 2)")
    p_serve.add_argument(
        "--fanout", type=int, default=8,
        help="max requests per micro-batched dispatch (scenario-axis lanes; "
             "default 8)")
    p_serve.add_argument(
        "--synthetic-nodes", type=int, default=0, metavar="N",
        help="serve a synthetic N-node cluster instead of a live snapshot "
             "(demos / load generation; no kubeconfig needed)")
    p_serve.add_argument(
        "--drain-deadline", type=float, default=None, metavar="SECONDS",
        help="graceful-drain budget on SIGTERM (default 25)")
    p_serve.add_argument(
        "--debug-faults", action="store_true",
        help="enable the POST /debug/fault-plan injection endpoint "
             "(testing/CI only)")
    p_serve.add_argument(
        "--xray", action="store_true",
        help="record per-request decision records; /v1/whatif responses "
             "then ride the flight recorder (GET /explain, /debug/vars)")
    p_serve.add_argument(
        "--no-scope", action="store_true",
        help="disable simonscope (request tracing + SLO engine + runtime "
             "telemetry sampler) — it is ON by default in serve mode; "
             "tracing-off serving reproduces bit-identical placements and "
             "byte-identical metrics")
    p_serve.add_argument(
        "--state-dir", default=None, metavar="DIR",
        help="simonha crash consistency: fsync every /v1/ingest delta to an "
             "epoch-numbered WAL in DIR before it mutates the image, "
             "checkpoint periodically, and on restart restore checkpoint + "
             "WAL tail to a bit-identical image (default: off, in-memory "
             "only)")
    p_serve.add_argument(
        "--staleness-ceiling", type=float, default=None, metavar="SECONDS",
        help="degraded mode serves the last consistent epoch at most this "
             "stale before /healthz flips 503 (default 120)")
    p_serve.add_argument(
        "--checkpoint-every", type=int, default=None, metavar="N",
        help="compact the WAL into a checkpoint every N ingest records "
             "(default 64)")
    p_serve.add_argument(
        "--max-queue", type=int, default=None, metavar="N",
        help="admission control: shed /v1/whatif with 429 once N requests "
             "are queued (default 256 in serve mode; deadline-aware "
             "shedding rides the same controller)")
    p_serve.add_argument(
        "--tenant-rate", type=float, default=None, metavar="RPS",
        help="per-(tenant, route) token-bucket rate limit in requests/s "
             "(0 = unlimited, the default)")
    p_serve.add_argument(
        "--ingest-max-bytes", type=int, default=None, metavar="BYTES",
        help="shed any /v1/ingest payload over BYTES with 413 before "
             "reading it (default 8 MiB; in-flight total bounded at 4x)")
    p_serve.add_argument(
        "--watch", default=None, metavar="SPEC",
        help="simonsync: keep the resident image current from a watch "
             "source instead of (only) /v1/ingest. SPEC is "
             "'file:stream.jsonl' (recorded JSONL replay), a chunked-HTTP "
             "watch URL (optionally 'watch_url|list_url' so 410-Gone can "
             "relist-reconcile), or 'kube' (watch the kubeconfig cluster's "
             "nodes+pods). Resumes from the persisted resourceVersion "
             "bookmark when --state-dir is set")

    p_slo = sub.add_parser(
        "slo", help="Render a running serve instance's SLO snapshot "
                    "(simonscope): per-endpoint rps, queue/dispatch/fetch/"
                    "total latency quantiles over the rolling window, SLO "
                    "targets and error-budget burn")
    p_slo.add_argument("--url", default="http://127.0.0.1:8080",
                       help="server base URL (default http://127.0.0.1:8080)")
    p_slo.add_argument("--json", action="store_true",
                       help="emit the raw /v1/serve/stats payload as JSON")

    p_top = sub.add_parser(
        "top", help="Refreshing terminal view of a running serve instance "
                    "(simonscope): rps, latency decomposition, lane "
                    "coalescing, route mix, device pool footprint")
    p_top.add_argument("--url", default="http://127.0.0.1:8080",
                       help="server base URL (default http://127.0.0.1:8080)")
    p_top.add_argument("--interval", type=float, default=2.0, metavar="S",
                       help="refresh period in seconds (default 2)")
    p_top.add_argument("--count", type=int, default=0, metavar="N",
                       help="exit after N refreshes (0 = until interrupted)")
    p_top.add_argument("--no-clear", action="store_true",
                       help="append frames instead of clearing the screen "
                            "(logs / CI)")

    p_pulse = sub.add_parser(
        "pulse", help="Render the simonpulse performance ledger: per-"
                      "dispatch wall decomposition, warm-wall MAD baselines "
                      "and flagged regressions, and the static roofline "
                      "cost table (cost_analysis FLOPs/bytes at the audit "
                      "buckets)")
    p_pulse.add_argument("--url", default="", metavar="URL",
                         help="fetch GET {URL}/v1/pulse from a running "
                              "server instead of reading locally")
    p_pulse.add_argument("--jsonl", default="", metavar="FILE",
                         help="summarize a spilled ledger file "
                              "(OPEN_SIMULATOR_PULSE_JSONL) offline")
    p_pulse.add_argument("--roofline", action="store_true",
                         help="print the static roofline table from the "
                              "audit goldens' cost census (every "
                              "HOT_KERNELS entry x bucket x mesh)")
    p_pulse.add_argument("--json", action="store_true",
                         help="emit the raw summary document as JSON")

    p_sweep = sub.add_parser(
        "sweep", help="Run a batched scenario sweep (simonsweep): N "
                      "independent what-if futures — drains, zone outages, "
                      "preemption storms, rollout waves, nodepool mixes, "
                      "Monte-Carlo workload draws — evaluated on the "
                      "scenario axis of a few fan-out dispatches, every "
                      "lane parity-checked against a fresh serial run")
    p_sweep.add_argument("spec", help="sweep spec file (YAML/JSON, kind: "
                                      "SweepSpec; see examples/sweeps/)")
    p_sweep.add_argument(
        "--seed", type=int, default=None, metavar="K",
        help="override the spec's seed: every random draw (Monte-Carlo "
             "replicas, drain picks, the parity sample) derives from it "
             "through explicit PRNG keys, so the same seed is byte-identical "
             "report JSON")
    p_sweep.add_argument(
        "--out", default="", metavar="FILE.json",
        help="write the full report as deterministic JSON")
    p_sweep.add_argument(
        "--json", action="store_true",
        help="print the report JSON on stdout instead of the summary table")
    p_sweep.add_argument(
        "--parity", choices=("full", "sample", "off"), default="full",
        help="batched==serial placement-census fuzzing: re-run every "
             "batched lane ('full', default), a seeded sample, or skip "
             "('off', bench timing only); any mismatch exits nonzero")
    p_sweep.add_argument(
        "--parity-sample", type=int, default=8, metavar="N",
        help="lanes re-run serially under --parity sample (default 8)")
    p_sweep.add_argument(
        "--fanout", type=int, default=64, metavar="S",
        help="max scenario lanes per batched dispatch (default 64)")

    sub.add_parser("version", help="Print the version of simon")

    p_doc = sub.add_parser("gen-doc", help="Generate markdown document for your project")
    p_doc.add_argument(
        "-d", "--output-directory", default="./docs/commandline",
        help="assign a directory to store documents",
    )
    return parser


def cmd_apply(args) -> int:
    from ..apply.applier import Applier, Options
    from ..utils.devices import enable_compilation_cache

    enable_compilation_cache()

    ext = [e.strip() for e in (args.extended_resources or "").split(",") if e.strip()]
    trace_out = getattr(args, "trace_out", "")
    metrics_out = getattr(args, "metrics_out", "")
    fault_plan = None
    xray_on = bool(getattr(args, "xray", False))
    if xray_on:
        from ..obs import xray

        xray.enable(getattr(args, "xray_out", "") or "simon-xray")
    try:
        if getattr(args, "fault_plan", ""):
            from ..resilience import FaultPlan, install_plan

            fault_plan = install_plan(FaultPlan.parse(args.fault_plan))
        applier = Applier(Options(
            simon_config=args.simon_config,
            default_scheduler_config=args.default_scheduler_config,
            use_greed=args.use_greed,
            interactive=args.interactive,
            extended_resources=ext,
            output_file=args.output_file,
            deadline=getattr(args, "deadline", 0.0) or 0.0,
            resume_journal=getattr(args, "resume_journal", "") or "",
        ))
        if trace_out:
            from ..utils.trace import start_collection

            start_collection()
        # simonscope CLI edge (OPEN_SIMULATOR_SCOPE=1): the apply run gets
        # one trace id, so engine schedule/probe spans group per run;
        # OPEN_SIMULATOR_SCOPE_OUT dumps the perfetto file afterwards
        # (failed runs included — scope.cli_edge owns the lifecycle)
        from ..obs import scope as scope_mod

        try:
            with scope_mod.cli_edge("cli:apply", config=args.simon_config):
                if args.profile:
                    import jax

                    with jax.profiler.trace(args.profile):
                        result = applier.run()
                else:
                    result = applier.run()
        finally:
            # dumps are written on FAILED runs too — a raising run records
            # failed=True spans, which is exactly when the trace matters —
            # and collection always stops (a leaked collector would grow for
            # the life of the process)
            if trace_out or metrics_out:
                from ..obs import REGISTRY

                if trace_out:
                    from ..obs.chrome import write_chrome_trace
                    from ..utils.trace import stop_collection

                    write_chrome_trace(trace_out, stop_collection(),
                                       metrics=REGISTRY.snapshot())
                if metrics_out:
                    with open(metrics_out, "w") as f:
                        json.dump(REGISTRY.snapshot(), f, indent=1)
                        f.write("\n")
        if result is not None and args.placement_dump:
            from ..parity import placement_dump, save_dump

            save_dump(placement_dump(result), args.placement_dump)
    except Exception as e:  # mirror `apply error: ...` + exit 1 (cmd/apply/apply.go:17-24)
        print(f"apply error: {e}", file=sys.stderr)
        return 1
    finally:
        if xray_on:
            # close on FAILED runs too — the partial trace is exactly the
            # evidence a failed run leaves behind
            from ..obs import xray

            rec = xray.active()
            counts = rec.counts() if rec is not None else {}
            xray.disable()
            # only point at the trace when something was actually recorded
            # (the JSONL is opened lazily on the first committed batch)
            if counts.get("batches"):
                print(f"xray: {counts.get('pods', 0)} decision records "
                      f"({counts.get('unscheduled', 0)} unscheduled, "
                      f"{counts.get('sets', 0)} decision sets) -> "
                      f"{counts.get('path')}.jsonl; query with "
                      f"`simon explain POD --trace {counts.get('path')}`",
                      file=sys.stderr)
        if fault_plan is not None:
            from ..resilience import clear_plan

            clear_plan()
            # the fired-injection trace on stderr: the replay-equality
            # artifact CI diffs across identical runs
            print(f"fault plan trace: {json.dumps(fault_plan.to_json()['trace'])}",
                  file=sys.stderr)
    # None = planning failed / user exited without a schedulable outcome; scripts
    # need a nonzero exit to distinguish it from success.
    return 0 if result is not None else 1


def cmd_lint(args) -> int:
    """simonlint — static analysis of JAX/TPU hazards (analysis/runner.py).
    Normally short-circuited in main(); this handles parse_args callers."""
    from ..analysis.runner import run_lint

    return run_lint(args.lint_args)


def cmd_audit(args) -> int:
    """simonaudit — compile-time dispatch certificates (analysis/hlo.py).
    Normally short-circuited in main(); this handles parse_args callers."""
    from ..analysis.hlo import run_audit

    return run_audit(args.audit_args)


def cmd_server(args) -> int:
    from ..server.http import Server
    from ..utils.devices import enable_compilation_cache

    enable_compilation_cache()

    try:
        server = Server(kubeconfig=args.kubeconfig, master=args.master,
                        debug_faults=True if args.debug_faults else None,
                        xray=True if getattr(args, "xray", False) else None)
        if args.grpc_port:
            # same Server object behind both surfaces: the TryLock busy
            # semantics hold across REST and gRPC clients
            from ..server.grpcbridge import GrpcBridge

            bridge = GrpcBridge(server=server)
            grpc_server, bound = bridge.build_grpc_server(args.grpc_port)
            grpc_server.start()
            print(f"simon grpc bridge listening on :{bound}")
        server.start(port=args.port,
                     drain_deadline=getattr(args, "drain_deadline", None))
    except KeyboardInterrupt:
        return 0
    except Exception as e:
        print(f"failed to start server: {e}", file=sys.stderr)
        return 1
    return 0


def cmd_serve(args) -> int:
    """`simon serve`: the `simon server` stack with resident what-if serving
    enabled — the image stages on the first /v1/whatif and stays current via
    /v1/ingest deltas. --synthetic-nodes N serves a generated cluster so the
    closed-loop load generator (tools/loadgen.py) and demos need no live
    kube-apiserver."""
    from ..server.http import ClusterSnapshot, Server
    from ..utils.devices import enable_compilation_cache

    enable_compilation_cache()
    snapshot_fn = None
    if args.synthetic_nodes:
        from ..core.types import ResourceTypes
        from ..utils.synth import synth_node

        n = int(args.synthetic_nodes)
        rt = ResourceTypes(nodes=[synth_node(i) for i in range(n)])
        snapshot_fn = lambda: ClusterSnapshot(rt, [], [], [])  # noqa: E731
    try:
        # simonscope is serve mode's default observability posture
        # (request tracing + SLO engine + runtime sampler); --no-scope /
        # OPEN_SIMULATOR_SCOPE=0 opts out
        from ..obs import scope as scope_mod

        scope_on = (False if getattr(args, "no_scope", False)
                    else scope_mod.env_enabled(default=True))
        server = Server(kubeconfig=args.kubeconfig, master=args.master,
                        snapshot_fn=snapshot_fn,
                        debug_faults=True if args.debug_faults else None,
                        xray=True if getattr(args, "xray", False) else None,
                        whatif=True, whatif_window_ms=args.window_ms,
                        whatif_fanout=args.fanout, scope=scope_on,
                        state_dir=getattr(args, "state_dir", None),
                        staleness_ceiling_s=getattr(
                            args, "staleness_ceiling", None),
                        checkpoint_every=getattr(
                            args, "checkpoint_every", None),
                        # serve mode bounds its queue by default: an
                        # unbounded admission queue is the exact hazard
                        # simonha closes (simonlint: unbounded-queue)
                        max_queue=(args.max_queue
                                   if getattr(args, "max_queue", None)
                                   is not None else 256),
                        tenant_rate=getattr(args, "tenant_rate", None),
                        ingest_max_bytes=getattr(
                            args, "ingest_max_bytes", None),
                        watch=getattr(args, "watch", None))
        if args.grpc_port:
            from ..server.grpcbridge import GrpcBridge

            bridge = GrpcBridge(server=server)
            grpc_server, bound = bridge.build_grpc_server(args.grpc_port)
            grpc_server.start()
            print(f"simon grpc bridge listening on :{bound}")
        server.start(port=args.port,
                     drain_deadline=getattr(args, "drain_deadline", None))
    except KeyboardInterrupt:
        return 0
    except Exception as e:
        print(f"failed to start serve: {e}", file=sys.stderr)
        return 1
    return 0


def cmd_sweep(args) -> int:
    """`simon sweep`: batched scenario sweeps over one resident cluster
    image, with the batched==serial parity fuzzer on by default."""
    import time

    from ..sweep import (
        SweepParityError,
        SweepRunner,
        SweepSpecError,
        build_report,
        load_spec,
        render_report,
        report_json,
    )
    from ..utils.devices import enable_compilation_cache

    enable_compilation_cache()
    try:
        spec = load_spec(args.spec)
    except SweepSpecError as e:
        print(f"sweep error: {e}", file=sys.stderr)
        return 1
    runner = SweepRunner(spec, seed=args.seed, parity=args.parity,
                         parity_sample=args.parity_sample,
                         fanout=args.fanout)
    # simonscope CLI edge (OPEN_SIMULATOR_SCOPE=1): the whole sweep becomes
    # one trace — chunk dispatch spans (sweep/runner.py) and engine probe
    # spans share the run's trace id; OPEN_SIMULATOR_SCOPE_OUT dumps the
    # perfetto file on exit, parity failures included (scope.cli_edge)
    from ..obs import scope as scope_mod

    t0 = time.perf_counter()
    try:
        with scope_mod.cli_edge("cli:sweep", spec=args.spec):
            runner.run()
    except SweepParityError as e:
        print(f"sweep PARITY FAILURE: {e}", file=sys.stderr)
        return 1
    except SweepSpecError as e:
        print(f"sweep error: {e}", file=sys.stderr)
        return 1
    wall = time.perf_counter() - t0
    report = build_report(runner)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(report_json(report))
    if args.json:
        sys.stdout.write(report_json(report))
    else:
        print(render_report(report))
    # wall time on stderr ONLY: the report (and --out bytes) must be
    # deterministic across runs of the same seed
    print(f"sweep: {len(report['scenarios'])} scenarios in {wall:.2f}s "
          f"({len(report['scenarios']) / wall:.1f} scenarios/s)"
          + (f" -> {args.out}" if args.out else ""), file=sys.stderr)
    return 0


def _load_metrics_snapshot(path: str) -> dict:
    """A registry snapshot from a --metrics-out dump or the metadata of a
    --trace-out Chrome trace. Raises ValueError on anything else."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "traceEvents" in doc:
        doc = (doc.get("metadata") or {}).get("metrics")
        if not doc:
            raise ValueError(f"{path}: trace file carries no metrics snapshot")
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a metrics snapshot")
    return doc


# Counter families whose GROWTH between two runs is a regression signal when
# comparing bench/CI dumps (everything here counts failures, rework, or
# compile churn — never useful work).
_BAD_WHEN_UP = (
    "simon_compile_cache_misses_total",
    "simon_xla_backend_compiles_total",
    "simon_commit_rollbacks_total",
    "simon_http_errors_total",
    "simon_retries_total",
    "simon_deadline_exceeded_total",
    "simon_faults_injected_total",
    "simon_guard_watchdog_expiries_total",
    "simon_guard_oom_bisections_total",
    "simon_guard_failovers_total",
    "simon_preemption_replay_pods_total",
    "simon_xray_dropped_total",
    # serving/scope rework-and-loss families (PR 14): stale sessions are
    # transparent re-encodes (rework), parity mismatches are correctness
    # failures, dropped trace events / sampler errors are observability loss
    "simon_serve_stale_sessions_total",
    "simon_sweep_parity_mismatches_total",
    "simon_scope_trace_dropped_total",
    "simon_scope_sampler_errors_total",
    # simonpulse (PR 18): a flagged warm-wall regression is a performance
    # defect by definition; evicted ledger records are observability loss
    "simon_pulse_regressions_total",
    "simon_pulse_records_dropped_total",
    # simonha (PR 19): a wrong-epoch answer or a WAL/checkpoint lineage
    # mismatch is a crash-consistency correctness failure
    "simon_serve_wrong_epoch_answers_total",
    "simon_serve_wal_parity_mismatches_total",
    # simonsync (PR 20): a post-reconcile parity mismatch is a correctness
    # failure; a relist falling back to a generation-bumping rebuild means
    # the columnar diff declined — a robustness regression
    "simon_sync_parity_mismatches_total",
    "simon_sync_full_rebuilds_total",
)


def _diff_metrics(snap_a: dict, snap_b: dict, out) -> Tuple[int, int]:
    """Render per-metric deltas A -> B; returns (changed, regressions)."""
    from ..obs import values_from_snapshot

    va, vb = values_from_snapshot(snap_a), values_from_snapshot(snap_b)
    fam_type: dict = {}
    for snap in (snap_a, snap_b):
        for name, fam in snap.items():
            fam_type[name] = fam.get("type", "untyped")
    # longest-match family lookup: flat keys are name{labels} (+_sum/_count)
    fams = sorted(fam_type, key=len, reverse=True)
    changed = regressions = backwards = 0

    def fmt(v: float) -> str:
        return str(int(v)) if float(v).is_integer() else f"{v:.6g}"

    for key in sorted(set(va) | set(vb)):
        a, b = va.get(key, 0.0), vb.get(key, 0.0)
        if a == b:
            continue
        changed += 1
        fam = next((n for n in fams if key.startswith(n)), "")
        delta = b - a
        flags = []
        if fam_type.get(fam) == "counter":
            if delta < 0:
                backwards += 1
                flags.append("counter went backwards (different baseline?)")
            elif any(fam.startswith(p) for p in _BAD_WHEN_UP):
                regressions += 1
                flags.append("REGRESSION")
        sign = "+" if delta >= 0 else ""
        print(f"{key}  {fmt(a)} -> {fmt(b)}  ({sign}{fmt(delta)})"
              + (f"  [{'; '.join(flags)}]" if flags else ""), file=out)
    print(f"# {changed} metric(s) changed, {regressions} regression(s), "
          f"{backwards} counter(s) went backwards", file=out)
    return changed, regressions


def cmd_metrics(args) -> int:
    """Render a saved registry snapshot (apply --metrics-out, or the metadata
    of a --trace-out Chrome trace) as Prometheus text on stdout — or, with
    --diff A B, the per-metric deltas between two dumps."""
    from ..obs import render_text_from_snapshot

    try:
        if args.diff:
            if len(args.snapshot) != 2:
                print("metrics error: --diff needs exactly two snapshot "
                      "files (A B)", file=sys.stderr)
                return 1
            _, regressions = _diff_metrics(
                _load_metrics_snapshot(args.snapshot[0]),
                _load_metrics_snapshot(args.snapshot[1]), sys.stdout)
            return 1 if regressions and args.fail_on_regression else 0
        if len(args.snapshot) != 1:
            print("metrics error: one snapshot file expected (use --diff "
                  "for two)", file=sys.stderr)
            return 1
        doc = _load_metrics_snapshot(args.snapshot[0])
    except (OSError, ValueError) as e:
        print(f"metrics error: {e}", file=sys.stderr)
        return 1
    sys.stdout.write(render_text_from_snapshot(doc))
    return 0


def cmd_explain(args) -> int:
    """Explain one pod's scheduling decision from a simonxray trace: the
    kube-scheduler-parity event line, per-plugin filter rejections, and the
    chosen-node score breakdown vs the runner-ups."""
    from ..obs import xray

    if not args.pod and not args.unscheduled:
        print("explain error: name a pod ('namespace/name') or pass "
              "--unscheduled", file=sys.stderr)
        return 1
    try:
        tr = xray.XrayTrace.load(args.trace)
    except (OSError, ValueError) as e:
        print(f"explain error: {e}", file=sys.stderr)
        return 1
    if args.unscheduled:
        rows = tr.unscheduled_summary()
        if args.json:
            print(json.dumps(rows, indent=1))
        else:
            for r in rows:
                print(f"{r['pod']}: {r['reason']}")
            print(f"# {len(rows)} unscheduled pod(s)")
        return 0
    exp = tr.explain(args.pod)
    if exp is None:
        print(f"explain error: no decision record for pod {args.pod!r} in "
              f"{args.trace} (run with --xray, and use 'namespace/name' "
              "when the bare name is ambiguous)", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(exp, indent=1, default=str))
    else:
        print(xray.render_explanation(exp))
    return 0


def _fetch_serve_stats(url: str) -> dict:
    """GET {url}/v1/serve/stats (the one snapshot `simon slo` and
    `simon top` are both built on)."""
    import urllib.error
    import urllib.request

    target = url.rstrip("/") + "/v1/serve/stats"
    try:
        with urllib.request.urlopen(target, timeout=10) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        raise RuntimeError(f"{target} -> HTTP {e.code}: {body}") from e
    except (urllib.error.URLError, OSError) as e:
        raise RuntimeError(f"{target}: {e}") from e


def _render_slo(stats: dict) -> str:
    """The `simon slo` table: per endpoint, windowed rps + phase quantiles +
    SLO budget accounting, from one /v1/serve/stats snapshot."""
    slo = stats.get("slo")
    if not slo:
        return ("no SLO data: simonscope is off on this server "
                "(start with `simon serve`, without --no-scope)")
    lines = [f"window: {slo.get('window_s', 0):g}s   epoch: "
             f"{stats.get('epoch', '?')}   nodes: {stats.get('nodes', '?')}"
             f"   queued: {stats.get('queued', 0)}"]
    for ep, d in sorted(slo.get("endpoints", {}).items()):
        routes = ", ".join(f"{r}={n}" for r, n in sorted(
            d.get("routes", {}).items()))
        lines.append(f"\n{ep}  ({d.get('rps', 0):g} rps; {routes})")
        lines.append(f"  {'phase':<10}{'count':>7}{'mean':>9}{'p50':>9}"
                     f"{'p95':>9}{'p99':>9}  (ms)")
        for phase in ("queue", "dispatch", "fetch", "total"):
            q = d.get("phases", {}).get(phase)
            if q is None:
                continue
            lines.append(
                f"  {phase:<10}{q['count']:>7}{q['mean_ms']:>9.2f}"
                f"{q['p50_ms']:>9.2f}{q['p95_ms']:>9.2f}{q['p99_ms']:>9.2f}")
        s = d.get("slo")
        if s:
            # availability-only targets leave target_p99_ms None (the
            # latency check then defaults to +inf in the engine)
            p99t = s.get("target_p99_ms")
            lines.append(
                f"  SLO: p99 target "
                f"{'—' if p99t is None else f'{p99t:g}ms'}, availability "
                f"{s['availability_target']:g} — {s['violations']}/"
                f"{s['requests']} violations, budget burn "
                f"{s['budget_burn']:g}x"
                + (" [BURNING]" if s["budget_burn"] > 1.0 else ""))
    sc = stats.get("scope") or {}
    pools = sc.get("pools") or {}
    if pools:
        lines.append("\ndevice pools: " + "  ".join(
            f"{k}={v / 1e6:.2f}MB" for k, v in sorted(pools.items())))
    if sc:
        lines.append(f"trace: {sc.get('trace_events', 0)} events buffered"
                     f" (cap {sc.get('trace_cap', 0)}); sampler "
                     f"{'on' if sc.get('sampler') else 'off'}")
    return "\n".join(lines)


def cmd_slo(args) -> int:
    """`simon slo`: one SLO snapshot from a running serve instance."""
    try:
        stats = _fetch_serve_stats(args.url)
    except RuntimeError as e:
        print(f"slo error: {e}", file=sys.stderr)
        return 1
    try:
        if args.json:
            print(json.dumps(stats, indent=1, sort_keys=True))
        else:
            print(_render_slo(stats))
    except BrokenPipeError:
        return 0  # `simon slo | head` closing the pipe early is fine
    return 0


def cmd_top(args) -> int:
    """`simon top`: the refreshing terminal view over the same snapshots
    `simon slo` renders once."""
    import time as _time

    n = 0
    try:
        while True:
            try:
                stats = _fetch_serve_stats(args.url)
                frame = _render_slo(stats)
            except RuntimeError as e:
                frame = f"top: {e}"
            if not args.no_clear and sys.stdout.isatty():
                sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
            print(f"simon top — {args.url}  "
                  f"(refresh {args.interval:g}s; ctrl-c to exit)")
            print(frame, flush=True)
            n += 1
            if args.count and n >= args.count:
                return 0
            _time.sleep(max(0.1, args.interval))
    except KeyboardInterrupt:
        return 0
    except BrokenPipeError:
        return 0  # `simon top | head` closing the pipe early is fine


def cmd_pulse(args) -> int:
    """`simon pulse`: render the performance ledger — from a running server
    (--url), a spilled JSONL file (--jsonl), or this process's Pulse (mostly
    useful under --roofline, which needs no live ledger at all)."""
    from ..obs import pulse

    if args.roofline:
        import jax

        kind = jax.devices()[0].device_kind
        rows = pulse.roofline_table(kind=kind)
        if not rows:
            print("pulse error: no cost data in the audit goldens — run "
                  "`simon audit --update` to (re)generate certificates "
                  "with a cost census", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(rows, indent=1, sort_keys=True))
        else:
            print(pulse.format_roofline(rows, kind))
        return 0
    if args.url:
        import urllib.error
        import urllib.request

        base = args.url.rstrip("/")
        if "://" not in base:
            base = "http://" + base
        target = base + "/v1/pulse"
        try:
            with urllib.request.urlopen(target, timeout=10) as resp:
                doc = json.loads(resp.read())
        except urllib.error.HTTPError as e:
            body = e.read().decode(errors="replace")
            print(f"pulse error: {target} -> HTTP {e.code}: {body}",
                  file=sys.stderr)
            return 1
        except (urllib.error.URLError, OSError) as e:
            print(f"pulse error: {target}: {e}", file=sys.stderr)
            return 1
    elif args.jsonl:
        recs = []
        try:
            with open(args.jsonl, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if line:
                        recs.append(json.loads(line))
        except (OSError, ValueError) as e:
            print(f"pulse error: {args.jsonl}: {e}", file=sys.stderr)
            return 1
        doc = pulse.summarize_records(recs)
    else:
        p = pulse.active()
        if p is None:
            print("pulse error: simonpulse is off in this process; use "
                  "--url against a server started with "
                  "OPEN_SIMULATOR_PULSE=1, --jsonl on a spilled ledger, "
                  "or --roofline for the static cost table",
                  file=sys.stderr)
            return 1
        doc = p.summary()
    try:
        if args.json:
            print(json.dumps(doc, indent=1, sort_keys=True))
        else:
            print(pulse.format_summary(doc))
    except BrokenPipeError:
        return 0  # `simon pulse | head` closing the pipe early is fine
    return 0


def cmd_version(_args) -> int:
    print(f"Version: {__version__}")
    print(f"Commit: {COMMIT_ID}")
    return 0


def cmd_gen_doc(args) -> int:
    """cobra doc.GenMarkdownTree equivalent: one markdown page per command."""
    out = args.output_directory
    if not os.path.isdir(out):
        print(f"Invalid output directory({out})", file=sys.stderr)
        return 1
    parser = build_parser()
    pages = {"simon": parser}
    for action in parser._subparsers._group_actions:  # noqa: SLF001
        for name, sp in action.choices.items():
            pages[f"simon_{name.replace('-', '_')}"] = sp
    for page, p in pages.items():
        with open(os.path.join(out, f"{page}.md"), "w") as f:
            title = page.replace("_", " ")
            f.write(f"## {title}\n\n{p.description or p.format_usage()}\n\n")
            f.write("```\n" + p.format_help() + "```\n")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    _init_logging()
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["lint"]:
        # Dispatch before argparse: REMAINDER would reject flags placed ahead
        # of the first path (`simon lint --format json pkg/`), and run_lint
        # owns its own --help.
        from ..analysis.runner import run_lint

        return run_lint(argv[1:])
    if argv[:1] == ["audit"]:
        # same REMAINDER workaround; run_audit owns its own --help, and must
        # set the virtual-CPU device flag before anything imports jax
        from ..analysis.hlo import run_audit

        return run_audit(argv[1:])
    parser = build_parser()
    args = parser.parse_args(argv)
    from ..parity import cmd_parity

    handlers = {
        "apply": cmd_apply,
        "audit": cmd_audit,
        "explain": cmd_explain,
        "lint": cmd_lint,
        "metrics": cmd_metrics,
        "serve": cmd_serve,
        "server": cmd_server,
        "slo": cmd_slo,
        "sweep": cmd_sweep,
        "top": cmd_top,
        "version": cmd_version,
        "gen-doc": cmd_gen_doc,
        "parity": cmd_parity,
        "pulse": cmd_pulse,
    }
    if not args.command:
        parser.print_help()
        return 0
    return handlers[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
