"""HTTP front end + CLI for the serving plane.

The reference exposed trained models through a RESTful endpoint
(veles/loader/restful.py) backed by the libZnicz C++ runtime; this is
the production-shaped rebuild: requests enter a bounded queue, the
micro-batcher coalesces them into bucketed engine batches, and the
telemetry needed to operate the thing is one GET away.

    POST /predict   {"input": [[...], ...], "timeout_s": 5}
                    -> 200 {"output": [...]}
                    |  400 bad request  | 503 queue full (backpressure)
                    |  504 deadline exceeded
    GET  /metrics       -> serving + engine counters (metrics.py schema)
    GET  /metrics.prom  -> process registry, Prometheus text (the fleet
                           aggregator's scrape target, ISSUE 11)
    GET  /trace.json    -> this worker's span ring, rank-anchored for
                           the fleet trace merge
    GET  /healthz   -> {"status": "ok"}  (200 while accepting traffic)
    GET  /livez     -> 200 while the process serves HTTP at all (the
                       fleet router's restart probe, ISSUE 13)
    GET  /readyz    -> 200 ready + package fingerprint | 503 draining
                       (the fleet router's routing gate)
    GET  /          -> model metadata (PredictionServer-compatible)

CLI:  python -m znicz_tpu serve <package.npz> [--port N] [--max-batch N]
          [--max-wait-ms F] [--max-queue N] [--native] [--no-warmup]
          [--no-aot]

A package carrying ahead-of-time executables (``python -m znicz_tpu
aot``, docs/COMPILE.md) boots with ``compile_count == 0`` when its
backend fingerprint matches this host; otherwise the loader logs the
mismatch and warmup JIT-compiles each bucket through the persistent
compilation cache as before.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from znicz_tpu.core.logger import Logger
from znicz_tpu.observe import trace as _trace
from znicz_tpu.observe.federation import next_request_id, request_track
from znicz_tpu.serve.batcher import DeadlineExceeded, MicroBatcher, QueueFull
from znicz_tpu.serve.engine import BatchEngine, load_backend


class _JsonHandler(BaseHTTPRequestHandler):
    """Shared HTTP scaffolding for both serving planes: silent access
    log, one JSON reply helper, one healthz shape — the predict and
    generate front ends must never drift on the envelope load balancers
    and scrapers read."""

    def log_message(self, *args):
        pass

    def _reply(self, code: int, doc: dict, headers=()) -> None:
        body = json.dumps(doc).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers:
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _reply_healthz(self, draining: bool) -> None:
        self._reply(503 if draining else 200,
                    {"status": "draining" if draining else "ok"})

    # -- liveness vs readiness (ISSUE 13) ------------------------------------
    # The fleet router routes on READINESS and restarts on LIVENESS,
    # the two questions k8s-style probes keep distinct: a draining or
    # mid-reboot worker is alive (do not replace it) but must stop
    # receiving traffic before its drain completes.  /healthz keeps its
    # historical shape (alive-and-accepting) for existing monitors.
    def _reply_livez(self) -> None:
        """``GET /livez``: 200 while the process serves HTTP at all —
        draining included.  Only a dead listener fails this probe."""
        self._reply(200, {"status": "ok"})

    def _reply_readyz(self, draining: bool, package=None) -> None:
        """``GET /readyz``: 200 only while this worker should receive
        NEW traffic; carries the package fingerprint so a rolling
        weight update can gate on what the worker actually serves."""
        doc = {"status": "draining" if draining else "ready"}
        if package is not None:
            doc["package"] = package
        self._reply(503 if draining else 200, doc)

    def _request_id(self) -> str:
        """The request's trace id: honor an ``X-Request-Id`` minted
        upstream (the fleet router's router->worker correlation key,
        ISSUE 13) so every phase span of one request shares a track
        across processes; mint one only at the true admission edge."""
        return self.headers.get("X-Request-Id") or next_request_id()

    def _reply_prom(self) -> None:
        """``GET /metrics.prom``: the process-global registry in
        Prometheus text — the fleet aggregator's scrape target on BOTH
        serving planes (ISSUE 11)."""
        from znicz_tpu.observe import REGISTRY

        body = REGISTRY.render_prometheus().encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_trace(self) -> None:
        """``GET /trace.json``: this worker's tracer ring (request
        phase spans included), rank-anchored so
        ``federation.merge_traces`` / ``/fleet/trace.json`` can align
        it with its peers."""
        from znicz_tpu.observe import TRACER

        self._reply(200, TRACER.export_dict())


class ServeServer(Logger):
    """The assembled serving plane: engine + batcher + HTTP."""

    def __init__(self, model, port: int = 0, max_batch: int | None = None,
                 max_wait_ms: float = 2.0, max_queue: int = 128,
                 default_timeout_s: float = 30.0,
                 warmup: bool = True, package_info: dict | None = None,
                 feedback=None) -> None:
        super().__init__()
        #: content fingerprint of the package this worker booted from
        #: (utils/naming.py package_fingerprint) — served on /readyz so
        #: rolling weight updates can verify adoption (ISSUE 13)
        self.package_info = package_info
        #: learn-plane spool (ISSUE 14): answered predictions append as
        #: labeled (input, output) pairs with request-id provenance
        self.feedback = feedback
        if isinstance(model, BatchEngine):
            if max_batch is not None and max_batch != model.max_batch:
                raise ValueError(
                    f"max_batch={max_batch} conflicts with the supplied "
                    f"engine's max_batch={model.max_batch}; configure it "
                    "on the engine")
            self.engine = model
        else:
            self.engine = BatchEngine(
                model, max_batch=64 if max_batch is None else max_batch)
        if warmup and self.engine.input_shape is not None:
            self.engine.warmup()
        self.batcher = MicroBatcher(self.engine, max_wait_ms=max_wait_ms,
                                    max_queue=max_queue,
                                    default_timeout_s=default_timeout_s)
        self.metrics = self.batcher.metrics
        self.port = int(port)
        self._httpd = None
        self._thread = None

    # -- payloads ------------------------------------------------------------
    def metrics_snapshot(self) -> dict:
        """Serving + engine counters — the ``GET /metrics`` document,
        also registered into web_status.py's ``/status.json``."""
        return {"serving": self.metrics.snapshot(),
                "engine": self.engine.stats()}

    def meta_snapshot(self) -> dict:
        return {"model": self.engine.meta,
                "n_requests": self.metrics.admitted,
                "max_batch": self.engine.max_batch,
                "package": self.package_info}

    # -- HTTP ----------------------------------------------------------------
    def start(self) -> int:
        plane = self

        class Handler(_JsonHandler):
            def do_GET(self):
                if self.path.startswith("/metrics.prom"):
                    self._reply_prom()
                elif self.path.startswith("/metrics"):
                    self._reply(200, plane.metrics_snapshot())
                elif self.path.startswith("/trace.json"):
                    self._reply_trace()
                elif self.path.startswith("/livez"):
                    self._reply_livez()
                elif self.path.startswith("/readyz"):
                    self._reply_readyz(plane.batcher.draining,
                                       plane.package_info)
                elif self.path.startswith("/healthz"):
                    self._reply_healthz(plane.batcher.draining)
                else:
                    self._reply(200, plane.meta_snapshot())

            def do_POST(self):
                if not self.path.startswith("/predict"):
                    self._reply(404, {"error": "POST /predict"})
                    return
                rid = self._request_id()     # router-minted or admission
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    doc = json.loads(self.rfile.read(n))
                    future = plane.batcher.submit(
                        doc["input"], timeout_s=doc.get("timeout_s"),
                        request_id=rid)
                except QueueFull as exc:
                    self._reply(503, {"error": str(exc)},
                                headers=(("Retry-After", "1"),))
                    return
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as exc:
                    self._reply(400, {"error": str(exc)})
                    return
                try:
                    out = future.result()
                except DeadlineExceeded as exc:
                    self._reply(504, {"error": str(exc)})
                    return
                except QueueFull as exc:    # non-drain shutdown flushed it
                    self._reply(503, {"error": str(exc)},
                                headers=(("Retry-After", "1"),))
                    return
                except Exception as exc:  # noqa: BLE001 — engine failure
                    self._reply(500, {"error": str(exc)})
                    return
                out_rows = np.asarray(out).tolist()
                if plane.feedback is not None:
                    try:
                        plane.feedback.append_predict(
                            rid, doc["input"], out_rows)
                    except Exception as exc:  # noqa: BLE001 — feedback
                        plane.warning(     # must never fail a request
                            f"feedback append failed: {exc!r}")
                self._reply(200, {"output": out_rows},
                            headers=(("X-Request-Id", rid),))

        self._httpd = ThreadingHTTPServer(("127.0.0.1", self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="serve-http")
        self._thread.start()
        self.info(f"serving on http://127.0.0.1:{self.port}/ "
                  f"(buckets {list(self.engine.buckets)})")
        return self.port

    def stop(self, drain: bool = True) -> None:
        """Graceful shutdown, in load-balancer-observable order: the
        batcher drains FIRST — while it does, ``/healthz`` answers 503
        "draining" and new ``/predict`` admissions get 503 QueueFull —
        then the listener closes, and the engine backend is released
        only if the drain actually finished (a worker still grinding
        through the queue must not lose its backend mid-batch)."""
        drained = self.batcher.stop(drain=drain)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if drained:
            self.engine.close()
        else:
            self.warning("drain still in progress past the join timeout;"
                         " leaving the engine open for the worker")


# -- generative serving plane (ISSUE 10) -------------------------------------

def encode_chars(text: str, charmap) -> list:
    """THE charmap text encoder (id <- character), shared by the HTTP
    front end and the CLI so out-of-vocab handling cannot drift: every
    character must be in the model's vocab — unknown characters fail
    loudly instead of aliasing to id 0."""
    stoi = {c: i for i, c in enumerate(charmap)}
    missing = sorted({c for c in text if c not in stoi})
    if missing:
        raise ValueError(f"prompt contains characters outside the "
                         f"model vocab: {missing[:8]!r}")
    return [stoi[c] for c in text]

class GenerateServer(Logger):
    """The assembled generative plane: KV-cache decoder + continuous
    batcher + streaming HTTP.

    ::

        POST /generate  {"prompt": "text"} | {"tokens": [ids]},
                        "max_tokens": 32, "temperature": 0.0,
                        "top_k": 0, "seed": 0, "timeout_s": 60,
                        "stream": true
            -> 200 ndjson stream: {"token": id[, "text": "c"]} per
               token, then EXACTLY ONE terminal line — {"done": true,
               "reason": "length", "n_tokens": N} or the error sentinel
               {"error": "...", "done": true} (a stream NEVER just goes
               quiet — the chaos drill pins this)
            |  200 single JSON document with "stream": false
            |  400 bad input | 503 queue full | 504 deadline (non-
               stream mode; streamed deadlines arrive as the sentinel)
        GET  /metrics       -> {"generate": ..., "decoder": ...}
        GET  /metrics.prom  -> process registry, Prometheus text
        GET  /trace.json    -> span ring incl. per-request phase spans
                               (queue/prefill/decode/stream, linked by
                               request id on one synthetic track)
        GET  /healthz       -> 200 ok | 503 draining
        GET  /livez         -> 200 while the process serves HTTP
        GET  /readyz        -> 200 ready + package fingerprint
                               | 503 draining (router routing gate)
        GET  /              -> model metadata

    ``charmap`` (id -> character, from the LM package) enables text
    prompts and per-token ``"text"`` fields; tokens-only models speak
    raw ids.
    """

    def __init__(self, batcher, charmap=None, port: int = 0,
                 name: str = "lm", package_info: dict | None = None) -> None:
        super().__init__()
        #: /readyz fingerprint, same contract as ServeServer (ISSUE 13)
        self.package_info = package_info
        self.batcher = batcher
        self.decoder = batcher.decoder
        self.metrics = batcher.metrics
        self.name = name
        self.charmap = list(charmap) if charmap else None
        self.port = int(port)
        self._httpd = None
        self._thread = None

    # -- text codec ----------------------------------------------------------
    def encode(self, text: str) -> list:
        if self.charmap is None:
            raise ValueError("this model has no charmap; send "
                             "{\"tokens\": [...]} instead of a text "
                             "prompt")
        return encode_chars(text, self.charmap)

    def decode_text(self, ids) -> str:
        if self.charmap is None:
            return ""
        return "".join(self.charmap[i] for i in ids
                       if 0 <= i < len(self.charmap))

    # -- payloads ------------------------------------------------------------
    def metrics_snapshot(self) -> dict:
        return {"generate": self.metrics.snapshot(),
                "decoder": self.decoder.stats()}

    def meta_snapshot(self) -> dict:
        return {"model": {"name": self.name, "kind": "lm",
                          "vocab": self.decoder.vocab,
                          "charmap": self.charmap is not None},
                "max_len": self.decoder.max_len,
                "slots": self.decoder.batch,
                "paged": bool(getattr(self.decoder, "paged", False)),
                "speculative": self.batcher._draft is not None,
                "package": self.package_info,
                "n_requests": self.metrics.snapshot()["admitted"]}

    def _submit_doc(self, doc: dict, request_id: str | None = None):
        """Parse one /generate body and admit it; returns the stream.
        Raises ValueError (400) / QueueFull (503)."""
        if "tokens" in doc:
            ids = [int(t) for t in doc["tokens"]]
        elif "prompt" in doc:
            ids = self.encode(str(doc["prompt"]))
        else:
            raise ValueError('body needs "prompt" or "tokens"')
        return self.batcher.submit(
            ids,
            max_new_tokens=int(doc.get("max_tokens", 32)),
            temperature=float(doc.get("temperature", 0.0)),
            top_k=int(doc.get("top_k", 0)),
            seed=int(doc.get("seed", 0)),
            timeout_s=doc.get("timeout_s"),
            request_id=request_id)

    # -- HTTP ----------------------------------------------------------------
    def start(self) -> int:
        plane = self

        class Handler(_JsonHandler):
            def do_GET(self):
                if self.path.startswith("/metrics.prom"):
                    self._reply_prom()
                elif self.path.startswith("/metrics"):
                    self._reply(200, plane.metrics_snapshot())
                elif self.path.startswith("/trace.json"):
                    self._reply_trace()
                elif self.path.startswith("/livez"):
                    self._reply_livez()
                elif self.path.startswith("/readyz"):
                    self._reply_readyz(plane.batcher.draining,
                                       plane.package_info)
                elif self.path.startswith("/healthz"):
                    self._reply_healthz(plane.batcher.draining)
                else:
                    self._reply(200, plane.meta_snapshot())

            def _slack(self, timeout_s) -> float:
                """How long to wait on the stream before declaring the
                worker wedged: the request's own deadline (explicit, or
                the batcher's configured default — NOT a hardcoded
                constant a --timeout-s flag would silently undercut)
                plus grace."""
                return (timeout_s or plane.batcher.default_timeout_s
                        or 60.0) + 30.0

            def _stream_events(self, stream, timeout_s) -> None:
                """ndjson relay: every event the batcher emits becomes
                one flushed line; a client that hangs up cancels the
                generation (abandoned-request accounting).  The relay
                itself is the request's ``generate.stream`` phase span
                — queue/prefill/decode cover the worker side, this one
                covers the wire."""
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("X-Request-Id", stream.request_id)
                self.end_headers()      # no Content-Length: close-delimited
                # terminal events are guaranteed; the slack only guards
                # a wedged worker from pinning this handler thread
                slack = self._slack(timeout_s)
                t_stream = time.perf_counter()
                n_events = 0
                try:
                    while True:
                        try:
                            event = stream.next_event(timeout=slack)
                        except TimeoutError:
                            # the client gets a terminal error NOW;
                            # cancel so a later-recovering worker frees
                            # the slot instead of decoding for a gone
                            # client
                            stream.cancel()
                            event = {"error": "stream stalled (worker "
                                     "unresponsive)", "done": True}
                        if "token" in event and plane.charmap is not None:
                            event = {**event, "text":
                                     plane.decode_text([event["token"]])}
                        try:
                            self.wfile.write(
                                (json.dumps(event) + "\n").encode())
                            self.wfile.flush()
                            n_events += 1
                        except (BrokenPipeError, ConnectionResetError,
                                OSError):
                            stream.cancel()  # client hung up: free the
                            return           # slot, count it abandoned
                        if event.get("done"):
                            return
                finally:
                    _trace.TRACER.complete(
                        "generate.stream", t_stream,
                        time.perf_counter() - t_stream,
                        tid=request_track(stream.request_id),
                        rid=stream.request_id, events=n_events)

            def do_POST(self):
                if not self.path.startswith("/generate"):
                    self._reply(404, {"error": "POST /generate"})
                    return
                rid = self._request_id()     # router-minted or admission
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    doc = json.loads(self.rfile.read(n))
                    if not isinstance(doc, dict):
                        raise ValueError("body must be a JSON object")
                    stream = plane._submit_doc(doc, request_id=rid)
                except QueueFull as exc:
                    self._reply(503, {"error": str(exc)},
                                headers=(("Retry-After", "1"),))
                    return
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as exc:
                    self._reply(400, {"error": str(exc)})
                    return
                if doc.get("stream", True):
                    self._stream_events(stream, doc.get("timeout_s"))
                    return
                from znicz_tpu.serve.continuous import GenerationError
                try:
                    ids = stream.result(
                        timeout_s=self._slack(doc.get("timeout_s")))
                except GenerationError as exc:
                    code = 504 if "deadline" in str(exc) else 500
                    self._reply(code, {"error": str(exc),
                                       "n_tokens": len(stream.tokens)})
                    return
                except TimeoutError as exc:
                    stream.cancel()     # free the slot for a client
                    self._reply(500, {"error": str(exc)})  # that's gone
                    return
                self._reply(200, {"tokens": ids,
                                  "text": plane.decode_text(ids),
                                  "reason": "length",
                                  "n_tokens": len(ids),
                                  "request_id": stream.request_id},
                            headers=(("X-Request-Id",
                                      stream.request_id),))

        self._httpd = ThreadingHTTPServer(("127.0.0.1", self.port),
                                          Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="generate-http")
        self._thread.start()
        self.info(f"generating on http://127.0.0.1:{self.port}/ "
                  f"({self.decoder.batch} slots, max_len "
                  f"{self.decoder.max_len})")
        return self.port

    def stop(self, drain: bool = True) -> None:
        """Same load-balancer-observable order as ``ServeServer``: the
        batcher drains first (healthz says 503 draining, new /generate
        admissions 503), then the listener closes."""
        self.batcher.stop(drain=drain)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


# -- CLI ---------------------------------------------------------------------

def build_generate_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="znicz_tpu generate",
        description="generate tokens from an LM package — one-shot to "
                    "stdout, or a streaming HTTP server with "
                    "continuous batching")
    p.add_argument("package", help="path to a utils/export.py LM "
                                   "package (export_lm / char_lm "
                                   "lm_export)")
    p.add_argument("--prompt", default=None,
                   help="text prompt (one-shot mode unless --serve)")
    p.add_argument("--tokens", default=None,
                   help="comma-separated token ids instead of --prompt")
    p.add_argument("--max-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 samples (seeded, reproducible)")
    p.add_argument("--top-k", type=int, default=0,
                   help="truncate sampling to the k most likely (0 = "
                        "full vocab)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-len", type=int, default=256,
                   help="cache-length ceiling (prompt + generation)")
    p.add_argument("--serve", action="store_true",
                   help="serve POST /generate with continuous batching "
                        "instead of a one-shot generation")
    p.add_argument("--port", type=int, default=8080,
                   help="listen port (0 picks a free one)")
    p.add_argument("--slots", type=int, default=4,
                   help="decode-batch width (concurrent generations)")
    p.add_argument("--max-queue", type=int, default=64,
                   help="requests waiting for a slot; beyond it -> 503")
    p.add_argument("--timeout-s", type=float, default=60.0,
                   help="default per-request deadline")
    p.add_argument("--no-paged", action="store_true",
                   help="serve from per-slot contiguous cache buckets "
                        "instead of the block-paged KV arena")
    p.add_argument("--page-size", type=int, default=16,
                   help="KV arena rows per page (paged serving)")
    p.add_argument("--arena-pages", type=int, default=0,
                   help="total KV arena pages shared by all slots "
                        "(0 = worst case: slots x max_len rows); "
                        "smaller values bank on the long tail and set "
                        "the real slot ceiling")
    p.add_argument("--speculative", action="store_true",
                   help="speculative decoding: the package's draft "
                        "model (or --draft-layers) proposes, the "
                        "target verifies — greedy output is "
                        "token-identical to plain decode")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens proposed per speculative round")
    p.add_argument("--draft-layers", type=int, default=0,
                   help="with --speculative and no draft in the "
                        "package: truncate the target to its first N "
                        "layers as the draft")
    p.add_argument("--pallas-decode", action="store_true",
                   help="route single-query decode attention through "
                        "the Pallas flash-decode kernel (interpret "
                        "mode off-TPU; on a TPU a page size or head "
                        "dim it cannot compile is an error)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip pre-compiling the cache buckets")
    p.add_argument("--feedback-spool", default=None, metavar="DIR",
                   help="append every COMPLETED generation (prompt + "
                        "continuation + request id) to this learn-"
                        "plane spool directory (docs/LEARNING.md) — "
                        "the train-while-serve feedback source")
    p.add_argument("--smoke-test", action="store_true",
                   help="start, stream one self-request, exit (CI "
                        "probe)")
    return p


def _parse_prompt(args, charmap) -> list:
    if args.tokens is not None:
        return [int(t) for t in args.tokens.split(",") if t.strip()]
    if args.prompt is None:
        raise ValueError("need --prompt or --tokens")
    if not charmap:
        raise ValueError("this package has no charmap; use --tokens")
    return encode_chars(args.prompt, charmap)


def generate_main(argv) -> int:
    from znicz_tpu.serve.continuous import ContinuousBatcher
    from znicz_tpu.serve.kvcache import KVDecoder, TokenSampler
    from znicz_tpu.utils.export import load_lm

    args = build_generate_parser().parse_args(argv)
    try:
        params, meta = load_lm(args.package)
    except (OSError, ValueError, KeyError) as exc:
        print(f"generate: cannot load {args.package!r}: {exc}")
        return 2
    charmap = meta.get("charmap")
    serve_mode = args.serve or args.smoke_test
    if not serve_mode:
        # one-shot: stream the generation to stdout as it decodes
        try:
            ids = _parse_prompt(args, charmap)
            decoder = KVDecoder(params, heads=meta["heads"],
                                max_len=args.max_len, batch=1)
            sampler = TokenSampler(seed=args.seed,
                                   temperature=args.temperature,
                                   top_k=args.top_k)

            def on_token(tok: int) -> None:
                if charmap:
                    print(charmap[tok], end="", flush=True)
                else:
                    print(tok, end=" ", flush=True)

            out = decoder.generate(ids, args.max_tokens, sampler,
                                   on_token=on_token)
        except ValueError as exc:
            print(f"generate: {exc}")
            return 2
        print()
        print(json.dumps({"n_tokens": len(out),
                          "prompt_tokens": len(ids),
                          "decoder": decoder.stats()}),
              file=__import__("sys").stderr)
        return 0
    draft = None
    if args.no_paged:
        if args.speculative:
            print("generate: --speculative needs the paged arena "
                  "(drop --no-paged)")
            return 2
        decoder = KVDecoder(params, heads=meta["heads"],
                            max_len=args.max_len, batch=args.slots)
        if not args.no_warmup:
            decoder.warmup()
    else:
        from znicz_tpu.serve.paged import PagedKVDecoder, truncate_draft
        from znicz_tpu.utils.export import load_lm_draft

        try:
            decoder = PagedKVDecoder(
                params, heads=meta["heads"], max_len=args.max_len,
                batch=args.slots, page=args.page_size,
                arena_pages=args.arena_pages or None,
                use_pallas=args.pallas_decode)
        except ValueError as exc:
            # e.g. --pallas-decode with a geometry the kernel cannot
            # compile on this TPU: an error, never the jnp path
            print(f"generate: {exc}")
            return 2
        if args.speculative:
            if args.spec_k < 1:
                print(f"generate: --spec-k must be >= 1, got "
                      f"{args.spec_k}")
                return 2
            dparams, dmeta = load_lm_draft(args.package)
            dheads = dmeta["heads"] if dmeta else meta["heads"]
            if dparams is None and args.draft_layers:
                dparams = truncate_draft(params, args.draft_layers)
            if dparams is None:
                print("generate: --speculative needs a draft model in "
                      "the package (export_lm draft_params=...) or "
                      "--draft-layers N")
                return 2
            # the draft's k+1 single-query steps per round ARE the
            # flash-decode shape — the kernel flag covers both decoders
            draft = PagedKVDecoder(
                dparams, heads=dheads, max_len=args.max_len,
                batch=args.slots, page=args.page_size,
                arena_pages=args.arena_pages or None,
                use_pallas=args.pallas_decode)
        if not args.no_warmup:
            decoder.warmup(spec_k=args.spec_k if args.speculative
                           else None)
            if draft is not None:
                draft.warmup()
    on_complete = None
    if args.feedback_spool:
        # the learn plane's traffic tap (ISSUE 14): completed
        # generations land in the crash-safe spool the trainer tails
        from znicz_tpu.learn.spool import FeedbackSpool

        on_complete = FeedbackSpool(args.feedback_spool).append_generate
    batcher = ContinuousBatcher(decoder, max_queue=args.max_queue,
                                default_timeout_s=args.timeout_s,
                                draft=draft, spec_k=args.spec_k,
                                on_complete=on_complete)
    from znicz_tpu.utils.naming import package_fingerprint

    server = GenerateServer(batcher, charmap=charmap, port=args.port,
                            name=meta.get("name", "lm"),
                            package_info=package_fingerprint(args.package))
    port = server.start()
    if args.smoke_test:
        import urllib.request

        body = {"max_tokens": 8, "temperature": 0.0}
        if charmap:
            body["prompt"] = charmap[0]
        else:
            body["tokens"] = [0]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        lines = []
        with urllib.request.urlopen(req, timeout=60) as r:
            for raw in r:
                lines.append(json.loads(raw))
        ok = len(lines) >= 2 and lines[-1].get("done") and \
            all("token" in ln for ln in lines[:-1])
        print(json.dumps({"smoke": "ok" if ok else "bad", "port": port,
                          "events": len(lines),
                          "metrics": server.metrics_snapshot()}))
        server.stop()
        return 0 if ok else 1
    done = threading.Event()
    import signal

    prev = signal.signal(signal.SIGTERM, lambda *a: done.set())
    try:
        done.wait()
    except KeyboardInterrupt:
        pass
    try:
        # the handler stays installed THROUGH the drain: restoring the
        # default first would let a second SIGTERM (an impatient
        # supervisor, a k8s double-signal) kill the worker mid-drain
        # and lose every request it had admitted
        print("generate: draining...")
        server.stop()
    finally:
        signal.signal(signal.SIGTERM, prev)
    return 0


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="znicz_tpu serve",
        description="serve a forward package over HTTP with dynamic "
                    "micro-batching")
    p.add_argument("package", help="path to a utils/export.py .npz package")
    p.add_argument("--port", type=int, default=8080,
                   help="listen port (0 picks a free one)")
    p.add_argument("--max-batch", type=int, default=64,
                   help="largest coalesced batch (bucket ceiling)")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="how long an underfull batch waits for stragglers")
    p.add_argument("--max-queue", type=int, default=128,
                   help="queue bound in chunks; beyond it -> 503")
    p.add_argument("--timeout-s", type=float, default=30.0,
                   help="default per-request deadline")
    p.add_argument("--native", action="store_true",
                   help="serve through the C++ runtime (no JAX in the "
                        "request path) when buildable")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip pre-compiling the batch buckets")
    p.add_argument("--no-aot", action="store_true",
                   help="ignore embedded ahead-of-time executables and "
                        "JIT every bucket (docs/COMPILE.md)")
    p.add_argument("--feedback-spool", default=None, metavar="DIR",
                   help="append every answered prediction (labeled "
                        "input/output pair + request id) to this "
                        "learn-plane spool directory (docs/LEARNING.md)")
    p.add_argument("--smoke-test", action="store_true",
                   help="start, serve one self-request, exit (CI probe)")
    return p


def serve_main(argv) -> int:
    args = build_serve_parser().parse_args(argv)
    try:
        backend = load_backend(args.package, prefer_native=args.native,
                               aot=not args.no_aot)
    except (OSError, ValueError, RuntimeError) as exc:
        print(f"serve: cannot load {args.package!r}: {exc}")
        return 2
    from znicz_tpu.utils.naming import package_fingerprint

    feedback = None
    if args.feedback_spool:
        from znicz_tpu.learn.spool import FeedbackSpool

        feedback = FeedbackSpool(args.feedback_spool)
    server = ServeServer(backend, port=args.port, max_batch=args.max_batch,
                         max_wait_ms=args.max_wait_ms,
                         max_queue=args.max_queue,
                         default_timeout_s=args.timeout_s,
                         warmup=not args.no_warmup,
                         package_info=package_fingerprint(args.package),
                         feedback=feedback)
    port = server.start()
    if args.smoke_test:
        import urllib.request

        shape = server.engine.input_shape or (1,)
        x = np.zeros((2,) + tuple(shape), np.float32)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"input": x.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        ok = len(out["output"]) == 2
        print(json.dumps({"smoke": "ok" if ok else "bad",
                          "port": port,
                          "metrics": server.metrics_snapshot()}))
        server.stop()
        return 0 if ok else 1
    # serve until SIGTERM (docker/k8s stop) or Ctrl-C — both drain
    done = threading.Event()
    import signal

    prev = signal.signal(signal.SIGTERM, lambda *a: done.set())
    try:
        done.wait()
    except KeyboardInterrupt:
        pass
    try:
        # handler stays installed through the drain (see generate_main)
        print("serve: draining...")
        server.stop()
    finally:
        signal.signal(signal.SIGTERM, prev)
    return 0
