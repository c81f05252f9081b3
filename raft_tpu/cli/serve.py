"""Serving CLI: an HTTP front end over ``raft_tpu.serve.InferenceEngine``.

Run as ``python -m raft_tpu serve ...`` (or ``python -m raft_tpu.cli.serve``).

Protocol (stdlib-only on both ends, numpy's ``npz`` as the wire format —
flow is float32 and PNG-style encodings lose the sign/scale):

- ``POST /v1/flow``  body = ``np.savez(buf, image1=..., image2=...)``
  with two matching ``(H, W, 3)`` arrays (uint8 or float32, [0, 255]).
  Response 200: ``npz`` with ``flow`` ``(H, W, 2)`` float32 at the
  original resolution.  Response 429 when the bounded queue is full:
  ``Retry-After`` header plus a structured JSON body
  ``{"error", "queue_depth", "retry_after_s"}`` so clients can back
  off programmatically; 400 on malformed input.
- ``POST /v1/stream/{id}``  streaming video sessions
  (docs/SERVING.md "Streaming sessions"): body =
  ``np.savez(buf, image=...)`` with ONE ``(H, W, 3)`` frame.  The
  first POST for an unknown ``{id}`` opens the session (frame 0, no
  flow yet; optional query params ``iters`` and ``ttl_s``) and
  returns ``npz`` with ``frame=0``; every later POST returns ``npz``
  with ``flow`` (previous frame -> this frame), ``frame``, and
  ``warm`` (whether the warm-start fast path served it).  429/400 as
  above; 409 when the session already has a frame in flight.
- ``DELETE /v1/stream/{id}``  close the session; JSON summary
  ``{"session", "frames", "pairs", "warm_pairs"}``.  404 on unknown
  (or already-expired) ids — idle sessions self-evict after their
  TTL.

With ``--replicas N`` (N > 1) the same endpoints front a supervised
replica fleet (``raft_tpu/serve/fleet.py``): requests route through a
health-gated router with failover + optional hedging, ``/v1/healthz``
reports fleet readiness (200 while ANY replica serves), and
``/metrics`` aggregates every replica's registry with a ``replica``
label per sample.
- ``GET /v1/stats``  JSON engine snapshot (latency percentiles,
  pairs/sec/chip, per-bucket compile counts).
- ``GET /metrics``   Prometheus text exposition rendered from the same
  engine registry ``/v1/stats`` reads (docs/OBSERVABILITY.md has the
  metric catalog) — point a Prometheus scrape job here.
- ``GET /v1/healthz`` (alias ``/healthz``)  readiness, not just
  liveness: 200 ``ok`` while the engine accepts traffic AND the device
  worker is making progress; 503 + JSON detail (pending count, seconds
  since the last completed device batch) when requests are pending but
  no batch has completed within ``--stall-timeout-s`` — the serve-side
  stall signal a balancer should drain on.
- ``POST /debug/profile?seconds=S``  on-demand device profiling: runs a
  ``jax.profiler`` capture for S seconds (clamped to [0.05, 60]; one at
  a time — concurrent requests get 409) into
  ``<telemetry_dir>/xprof/serve-<ts>/`` and returns the artifact dir.
  Trace spans recorded during the capture carry an ``xprof=<dir>``
  attribute linking waterfall to device profile.

Distributed tracing (docs/OBSERVABILITY.md): with
``--trace-sample-rate`` > 0, each ``POST /v1/flow`` opens (or, given an
``X-Raft-Trace: <trace>-<span>-<s|d>`` request header, continues) a
trace whose tree spans router placement, hedging, failover, and the
device batch; the response echoes the ``X-Raft-Trace`` header so
callers can correlate.  ``scripts/trace_report.py`` reconstructs the
trees from the telemetry dir.

Example client::

    import io, urllib.request, numpy as np
    buf = io.BytesIO(); np.savez(buf, image1=im1, image2=im2)
    r = urllib.request.urlopen(
        urllib.request.Request("http://localhost:8080/v1/flow",
                               data=buf.getvalue(), method="POST"))
    flow = np.load(io.BytesIO(r.read()))["flow"]

Each HTTP connection gets its own handler thread
(``ThreadingHTTPServer``), so concurrent clients coalesce into the
engine's micro-batches exactly like in-process callers.
"""

from __future__ import annotations

import argparse
import io
import json
import math

from raft_tpu.cli import (add_arch_argument, arch_from_args,
                          parse_with_arch)


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="raft-tpu serve",
        description="RAFT-TPU online inference server: shape-bucketed "
                    "compile cache + dynamic micro-batching "
                    "(docs/SERVING.md)")
    p.add_argument("--model", default=None,
                   help="checkpoint directory (same layouts as the "
                        "evaluate CLI); omit for --random-init")
    p.add_argument("--random-init", action="store_true",
                   help="serve randomly initialized weights (load/smoke "
                        "testing without a checkpoint)")
    add_arch_argument(p)
    p.add_argument("--precision", default="bf16",
                   choices=["bf16", "fp32"])
    p.add_argument("--iters", type=int, default=32,
                   help="refinement iterations per request")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--batching", default="request",
                   choices=["request", "slot"],
                   help="request-level micro-batching, or continuous "
                        "batching at GRU-iteration granularity over "
                        "--slots persistent device lanes "
                        "(docs/SERVING.md 'Continuous batching')")
    p.add_argument("--slots", type=int, default=8,
                   help="slot mode: persistent device lanes per bucket")
    p.add_argument("--stream-ttl-s", type=float, default=60.0,
                   help="streaming sessions: evict a session (and free "
                        "its pinned lane) after this long without a "
                        "frame (docs/SERVING.md 'Streaming sessions')")
    p.add_argument("--stream-warm-iters", type=int, default=None,
                   help="streaming sessions: iteration budget for "
                        "warm-started frames (default: the session's "
                        "budget; warm frames also early-exit sooner "
                        "under --early-exit-threshold)")
    p.add_argument("--max-sessions", type=int, default=64,
                   help="open streaming sessions bound; beyond it "
                        "session opens get 429")
    p.add_argument("--early-exit-threshold", type=float, default=0.0,
                   help="slot mode: retire a request when its max flow "
                        "update falls below this (0 = always run the "
                        "full budget; pick a value the evaluate.py "
                        "--early_exit_threshold sweep cleared)")
    p.add_argument("--max-batch", type=int, default=8,
                   help="micro-batch size cap")
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="how long a micro-batch waits to fill after its "
                        "first request (latency/throughput knob)")
    p.add_argument("--max-queue", type=int, default=256,
                   help="in-flight bound; beyond it requests get 429")
    p.add_argument("--stall-timeout-s", type=float, default=120.0,
                   help="readiness threshold: with requests pending and "
                        "no device batch completed for this long, "
                        "GET /v1/healthz turns 503 (must exceed "
                        "max-wait-ms + worst cold compile, or warm up "
                        "first; 0 disables)")
    p.add_argument("--buckets", default=None,
                   help="comma-separated /8-aligned HxW bucket ladder "
                        "(e.g. 440x1024,720x1280); default: exact /8 "
                        "round-up per request shape")
    p.add_argument("--batch-sizes", default=None,
                   help="comma-separated compiled batch sizes "
                        "(default: powers of two up to --max-batch)")
    p.add_argument("--warmup", default=None,
                   help="comma-separated HxW image shapes to pre-compile "
                        "before accepting traffic")
    p.add_argument("--telemetry-dir", default=None,
                   help="write JSONL telemetry events (per-batch "
                        "records) into this directory; defaults to "
                        "$RAFT_TELEMETRY_DIR, unset = disabled")
    p.add_argument("--device-retries", type=int, default=1,
                   help="re-dispatches of a device batch after a "
                        "TRANSIENT error (flaky XLA/runtime dispatch) "
                        "before the batch fails; deterministic errors "
                        "always fail fast (docs/ROBUSTNESS.md)")
    p.add_argument("--retry-backoff-s", type=float, default=0.05,
                   help="base of the exponential retry ladder: retry k "
                        "sleeps this * 2^(k-1) (capped, jittered) "
                        "under the total retry deadline")
    p.add_argument("--chaos", default=None,
                   help="fault-injection spec, e.g. 'device_err@batch=3'"
                        " (docs/ROBUSTNESS.md grammar); default "
                        "$RAFT_CHAOS_SPEC, unset = no injection")
    p.add_argument("--chaos-seed", type=int, default=None,
                   help="seed for probabilistic chaos rules "
                        "(default $RAFT_CHAOS_SEED or 0)")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind a health-gated router "
                        "with failover (docs/SERVING.md fleet section); "
                        "1 = single engine, no fleet layer")
    p.add_argument("--remote", action="append", default=None,
                   metavar="HOST:PORT",
                   help="join a REMOTE serving host to the fleet as a "
                        "partition-tolerant replica behind the same "
                        "router (repeatable; docs/SERVING.md "
                        "'Multi-host fabric').  Implies fleet mode")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="elastic autoscaling bounds on LOCAL replicas "
                        "(e.g. 1:4): grow on sustained queue pressure, "
                        "shrink gracefully when idle (hysteresis + "
                        "cooldown; docs/SERVING.md 'Multi-host "
                        "fabric').  Implies fleet mode")
    p.add_argument("--aot-dir", default=None,
                   help="AOT executable artifact directory: replica 0 "
                        "exports its compiled executables here, every "
                        "later engine build imports them (zero-compile "
                        "warm start); default: fresh temp dir per fleet")
    p.add_argument("--hedge-timeout-s", type=float, default=0.0,
                   help="fleet mode: duplicate a still-unresolved "
                        "request onto a second replica after this many "
                        "seconds (0 = hedging off; set well above p99 "
                        "batch time)")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   help="distributed-tracing head-sample rate in [0, 1] "
                        "(0/unset = tracing off; errors, retries, and "
                        "hedges are tail-kept regardless once > 0); "
                        "default $RAFT_TRACE_SAMPLE_RATE")
    p.add_argument("--quality-sample-rate", type=float, default=0.0,
                   help="fraction of retiring slot-mode requests "
                        "scored with the label-free photometric "
                        "quality proxy (quality_score events, "
                        "raft_quality_* metrics, drift detection; "
                        "docs/OBSERVABILITY.md 'Flow quality'); "
                        "0 = scoring off, zero hot-path overhead")
    p.add_argument("--quality-cycle", action="store_true",
                   help="with --quality-sample-rate > 0: also run a "
                        "forward-backward cycle-consistency pass per "
                        "scored request (one extra inference on the "
                        "swapped frames)")
    return parse_with_arch(p, argv)


def _parse_hw_list(spec):
    out = []
    for tok in spec.split(","):
        h, w = tok.strip().lower().split("x")
        out.append((int(h), int(w)))
    return tuple(out)


def _make_handler(engine):
    # ``engine`` is a serving facade: a bare InferenceEngine or a
    # fleet's FlowRouter — both expose infer/health/stats/metrics_text
    # (and raise the same QueueFullError), so one handler serves both.
    import threading

    from http.server import BaseHTTPRequestHandler

    from raft_tpu.obs import trace
    from raft_tpu.serve import QueueFullError

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # One jax.profiler capture at a time (class-level: shared by
        # every handler thread of this server).
        _profile_lock = threading.Lock()

        def log_message(self, fmt, *args):  # stats() is the signal;
            pass                            # per-request stderr is noise

        def _reply(self, code, body, ctype, extra=()):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in extra:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _reply_json(self, code, obj, extra=()):
            self._reply(code, json.dumps(obj).encode(),
                        "application/json", extra)

        def do_GET(self):
            if self.path in ("/healthz", "/v1/healthz"):
                h = engine.health()
                if h["ready"]:
                    self._reply(200, b"ok", "text/plain")
                else:  # readiness: drain this replica
                    self._reply_json(503, h)
            elif self.path == "/v1/stats":
                self._reply_json(200, engine.stats())
            elif self.path == "/metrics":
                from raft_tpu.obs import PROMETHEUS_CONTENT_TYPE

                self._reply(200, engine.metrics_text().encode(),
                            PROMETHEUS_CONTENT_TYPE)
            else:
                self._reply_json(404, {"error": f"no route {self.path}"})

        def do_DELETE(self):
            if not self.path.startswith("/v1/stream/"):
                self._reply_json(404, {"error": f"no route {self.path}"})
                return
            sid = self.path[len("/v1/stream/"):]
            try:
                summary = engine.stream_close(sid)
            except ValueError as e:
                code = 404 if "unknown session" in str(e) else 409
                self._reply_json(code, {"error": str(e)})
                return
            except Exception as e:
                self._reply_json(
                    500, {"error": f"{type(e).__name__}: {e}"})
                return
            self._reply_json(200, summary)

        def _stream(self):
            """POST /v1/stream/{id} — open-on-first-use streaming
            frame (module docstring has the wire protocol)."""
            import numpy as np

            from urllib.parse import parse_qs, urlparse

            u = urlparse(self.path)
            sid = u.path[len("/v1/stream/"):]
            if not sid or "/" in sid:
                self._reply_json(404, {"error": f"no route {u.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                with np.load(io.BytesIO(self.rfile.read(n))) as z:
                    image = z["image"]
                qs = parse_qs(u.query)
                iters = (int(qs["iters"][0])
                         if "iters" in qs else None)
                ttl_s = (float(qs["ttl_s"][0])
                         if "ttl_s" in qs else None)
            except Exception as e:
                self._reply_json(400, {"error": f"bad stream "
                                                f"request: {e}"})
                return
            try:
                out = engine.stream_ingest(sid, image, iters=iters,
                                           ttl_s=ttl_s)
            except QueueFullError as e:
                retry_s = float(getattr(e, "retry_after_s", 1.0))
                self._reply_json(
                    429, {"error": str(e),
                          "queue_depth": int(getattr(e, "queue_depth",
                                                     0)),
                          "retry_after_s": retry_s},
                    extra=[("Retry-After",
                            str(max(1, math.ceil(retry_s))))])
                return
            except ValueError as e:
                code = 409 if "in flight" in str(e) else 400
                self._reply_json(code, {"error": str(e)})
                return
            except Exception as e:
                self._reply_json(
                    500, {"error": f"{type(e).__name__}: {e}"})
                return
            buf = io.BytesIO()
            if out["flow"] is None:
                np.savez(buf, frame=out["frame"], warm=False)
            else:
                np.savez(buf, flow=out["flow"], frame=out["frame"],
                         warm=out["warm"])
            self._reply(200, buf.getvalue(),
                        "application/octet-stream")

        def do_POST(self):
            import numpy as np

            if self.path.startswith("/debug/profile"):
                self._profile()
                return
            if self.path.startswith("/v1/stream/"):
                self._stream()
                return
            if self.path != "/v1/flow":
                self._reply_json(404, {"error": f"no route {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                with np.load(io.BytesIO(self.rfile.read(n))) as z:
                    im1, im2 = z["image1"], z["image2"]
            except Exception as e:
                self._reply_json(400, {"error": f"bad npz body: {e}"})
                return
            # Wire propagation: continue an upstream trace from the
            # X-Raft-Trace header (their sampling verdict wins), or
            # open a fresh root; the response echoes the header so the
            # caller can correlate.  Tracing off = the no-op singleton.
            tracer = trace.default_tracer()
            root = trace.NOOP_SPAN
            if tracer.enabled:
                up = trace.parse_header(self.headers.get(trace.HEADER))
                if up is not None:
                    root = tracer.start_trace(
                        "serve_http", trace_id=up[0], parent_id=up[1],
                        sampled=up[2], path=self.path)
                else:
                    root = tracer.start_trace("serve_http",
                                              path=self.path)
            hdr = trace.format_header(root)
            thdr = [(trace.HEADER, hdr)] if hdr else []
            try:
                with trace.use_context(root):
                    flow = engine.infer(im1, im2)
            except QueueFullError as e:
                root.end(status="full", error="QueueFullError")
                # Structured shed-load response: the client gets the
                # machine-readable backoff hint both as the standard
                # header (delta-seconds, so ceil) and in the body.
                retry_s = float(getattr(e, "retry_after_s", 1.0))
                self._reply_json(
                    429, {"error": str(e),
                          "queue_depth": int(getattr(e, "queue_depth", 0)),
                          "retry_after_s": retry_s},
                    extra=[("Retry-After",
                            str(max(1, math.ceil(retry_s))))] + thdr)
                return
            except ValueError as e:
                root.end(status="error", error="ValueError")
                self._reply_json(400, {"error": str(e)}, extra=thdr)
                return
            except Exception as e:
                root.end(status="error", error=type(e).__name__)
                self._reply_json(
                    500, {"error": f"{type(e).__name__}: {e}"},
                    extra=thdr)
                return
            root.end(status="ok")
            buf = io.BytesIO()
            np.savez(buf, flow=flow)
            self._reply(200, buf.getvalue(), "application/octet-stream",
                        extra=thdr)

        def _profile(self):
            """POST /debug/profile?seconds=S — on-demand jax.profiler
            capture into <telemetry>/xprof/serve-<ts>/ (409 while one
            is already running; spans recorded during the capture link
            to it via their xprof attribute)."""
            import os
            import tempfile
            import time
            from urllib.parse import parse_qs, urlparse

            qs = parse_qs(urlparse(self.path).query)
            try:
                seconds = float(qs.get("seconds", ["2"])[0])
            except ValueError:
                self._reply_json(400,
                                 {"error": "seconds must be a number"})
                return
            seconds = min(max(seconds, 0.05), 60.0)
            if not Handler._profile_lock.acquire(blocking=False):
                self._reply_json(
                    409, {"error": "a profile capture is already "
                                   "running; retry when it finishes"})
                return
            try:
                import jax

                from raft_tpu.obs import default_sink

                sink = default_sink()
                base = sink.directory if sink.enabled else \
                    tempfile.mkdtemp(prefix="raft-xprof-")
                outdir = os.path.join(
                    base, "xprof", time.strftime("serve-%Y%m%d-%H%M%S"))
                os.makedirs(outdir, exist_ok=True)
                jax.profiler.start_trace(outdir)
                trace.set_active_profile(outdir)
                try:
                    time.sleep(seconds)
                finally:
                    trace.set_active_profile(None)
                    jax.profiler.stop_trace()
                sink.emit("xprof_capture", source="serve", dir=outdir,
                          seconds=seconds)
                self._reply_json(200, {"dir": outdir,
                                       "seconds": seconds})
            except Exception as e:
                self._reply_json(
                    500, {"error": f"{type(e).__name__}: {e}"})
            finally:
                Handler._profile_lock.release()

    return Handler


def make_server(engine, host: str, port: int):
    """A ``ThreadingHTTPServer`` bound to ``host:port`` (port 0 picks a
    free port — tests), serving the engine (or a fleet router — see
    ``_make_handler``).  Caller owns lifecycle."""
    from http.server import ThreadingHTTPServer

    return ThreadingHTTPServer((host, port), _make_handler(engine))


def main(argv=None):
    args = parse_args(argv)
    if (args.model is None) == (not args.random_init):
        raise SystemExit("exactly one of --model / --random-init required")

    import os

    # Export before anything builds a default sink, so emitters without
    # an explicit sink (chaos fires) land next to the engine's events.
    if args.telemetry_dir:
        os.environ.setdefault("RAFT_TELEMETRY_DIR", args.telemetry_dir)

    from raft_tpu import chaos

    if args.chaos:
        os.environ[chaos.ENV_SPEC] = args.chaos
    if args.chaos_seed is not None:
        os.environ[chaos.ENV_SEED] = str(args.chaos_seed)
    chaos.install_from_env()

    import jax

    from raft_tpu.config import RAFTConfig
    from raft_tpu.serve import InferenceEngine, ServeConfig
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    model_cfg = RAFTConfig.preset(
        arch_from_args(args),
        compute_dtype="bfloat16" if args.precision == "bf16" else "float32")
    if args.model:
        from raft_tpu.cli.evaluate import load_model_variables

        variables = load_model_variables(args.model, model_cfg.arch)
        if "batch_stats" not in variables:
            variables = dict(variables, batch_stats={})
    else:
        from raft_tpu.models.raft import RAFT

        rng = jax.random.PRNGKey(0)
        img = jax.numpy.zeros((1, 64, 96, 3))
        variables = RAFT(model_cfg).init(
            {"params": rng, "dropout": rng}, img, img, iters=1)

    serve_cfg = ServeConfig(
        iters=args.iters, batching=args.batching, slots=args.slots,
        early_exit_threshold=max(args.early_exit_threshold, 0.0),
        stream_ttl_s=max(args.stream_ttl_s, 1e-3),
        stream_warm_iters=args.stream_warm_iters,
        max_sessions=max(args.max_sessions, 1),
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms, max_queue=args.max_queue,
        buckets=_parse_hw_list(args.buckets) if args.buckets else None,
        batch_sizes=tuple(int(b) for b in args.batch_sizes.split(","))
        if args.batch_sizes else None,
        stall_timeout_s=max(args.stall_timeout_s, 0.0),
        device_retries=max(args.device_retries, 0),
        retry_backoff_s=max(args.retry_backoff_s, 0.0),
        retry_backoff_max_s=max(ServeConfig.retry_backoff_max_s,
                                args.retry_backoff_s),
        quality_sample_rate=min(max(args.quality_sample_rate, 0.0),
                                1.0),
        quality_cycle=args.quality_cycle,
        # Fleet mode overrides this per engine build (FleetConfig owns
        # the artifact dir); single-engine mode imports at construction.
        aot_dir=args.aot_dir)
    sink = None
    if args.telemetry_dir:
        from raft_tpu.obs import EventSink

        sink = EventSink(args.telemetry_dir)
    trace_rate = (args.trace_sample_rate
                  if args.trace_sample_rate is not None
                  else float(os.environ.get("RAFT_TRACE_SAMPLE_RATE",
                                            "0") or 0))
    if trace_rate > 0:
        from raft_tpu.obs import trace

        trace.configure(sample_rate=trace_rate, sink=sink)
    autoscale = (0, 0)
    if args.autoscale:
        lo, sep, hi = args.autoscale.partition(":")
        if not sep or not lo.isdigit() or not hi.isdigit():
            raise SystemExit(
                f"--autoscale {args.autoscale!r}: expected MIN:MAX")
        autoscale = (int(lo), int(hi))
    if args.replicas > 1 or args.remote or args.autoscale:
        from raft_tpu.serve import (FleetConfig, FlowRouter,
                                    ReplicaFleet, RouterConfig)

        warmup = _parse_hw_list(args.warmup) if args.warmup else ()
        if args.warmup:
            print(f"fleet warmup: compiling {len(warmup)} shape(s) on "
                  "replica 0, AOT-importing on the rest...", flush=True)
        fleet = ReplicaFleet(
            variables, model_cfg, serve_cfg,
            FleetConfig(replicas=args.replicas, aot_dir=args.aot_dir,
                        warmup_shapes=warmup,
                        remote=tuple(args.remote or ()),
                        autoscale_min=autoscale[0],
                        autoscale_max=autoscale[1]),
            sink=sink)
        fleet.start()
        service = FlowRouter(
            fleet,
            RouterConfig(hedge_timeout_s=max(args.hedge_timeout_s, 0.0)),
            sink=sink)
        extra = (f", replicas={args.replicas}, "
                 f"aot_dir={fleet.aot_dir}")
        if args.remote:
            extra += f", remote={','.join(args.remote)}"
        if args.autoscale:
            extra += f", autoscale={autoscale[0]}:{autoscale[1]}"
    else:
        engine = InferenceEngine(variables, model_cfg, serve_cfg,
                                 sink=sink)
        engine.start()
        if args.warmup:
            shapes = _parse_hw_list(args.warmup)
            print(f"warmup: compiling {len(shapes)} shape(s)...",
                  flush=True)
            engine.warmup(shapes)
        fleet = None
        service = engine
        extra = ""

    server = make_server(service, args.host, args.port)
    host, port = server.server_address[:2]
    print(f"raft-tpu serve: listening on http://{host}:{port} "
          f"(backend={jax.default_backend()}, "
          f"batching={args.batching}, "
          f"max_batch={args.max_batch}, max_wait_ms={args.max_wait_ms}, "
          f"max_queue={args.max_queue}{extra})", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        if fleet is not None:
            fleet.stop()
        else:
            service.stop()
        print(json.dumps(service.stats()), flush=True)


if __name__ == "__main__":
    main()
