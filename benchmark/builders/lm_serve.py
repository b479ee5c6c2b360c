"""Builder kind ``lm_serve``: ``PagedKVDecoder`` + ``ContinuousBatcher``
as ``generate_main`` builds them, driven in process by an open-loop load
generator on the main thread (a copy of the in-process drive of
``chip_smoke.run_server``, without the HTTP hop).

Weights come from ``--seed`` through the configuration's reference module,
made on the device in one call; the decoder's constructor takes host
arrays and places them itself, so they go through the host once
(PERF.md lists that round trip for a later PR).

Times: a request is timed from the instant it was DUE, not from when it
left the generator.  Each token is stamped where the batcher hands it to
the request's ``TokenStream`` (the benchmark wraps ``_push_token`` for the
run), before any client thread would wake.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchlib import BenchmarkError, judge, percentile


class _Stamps:
    """Wraps ``TokenStream._push_token`` for the run: every token a
    stream receives is stamped on the stream itself."""

    def __enter__(self):
        from znicz_tpu.serve.continuous import TokenStream

        self._cls, self._orig = TokenStream, TokenStream._push_token
        orig = self._orig

        def stamped(stream, token):
            stream.__dict__.setdefault("bench_stamps", []).append(
                time.perf_counter())
            orig(stream, token)

        TokenStream._push_token = stamped
        return self

    def __exit__(self, *exc) -> None:
        self._cls._push_token = self._orig


class Cell:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.ref = ctx.roots.module("reference", self.cfg["reference"])
        self.gen = ctx.roots.module("generators", self.traffic["generator"])
        self.opts = self.cfg["builders"]["lm_serve"]
        self.readings: dict = {}

    # -- set-up ---------------------------------------------------------------
    def _boot(self) -> None:
        import jax

        from znicz_tpu.serve.continuous import ContinuousBatcher
        from znicz_tpu.serve.paged import PagedKVDecoder

        cfg, opts = self.cfg, self.opts
        params = jax.device_get(self.ref.init_params(self.ctx.seed, cfg))
        self.decoder = PagedKVDecoder(
            params, heads=int(cfg["n_head"]), max_len=int(opts["max_len"]),
            batch=int(opts["slots"]), page=int(opts["page"]),
            arena_pages=int(opts["arena_pages"]))
        del params
        self.batcher = ContinuousBatcher(
            self.decoder, max_queue=int(opts["max_queue"]),
            default_timeout_s=float(opts["timeout_s"]))

    def _warm(self, n: int) -> None:
        """Compile or load exactly the programs this mix can reach: the
        prefill and adopt program of every prompt bucket in the multiset,
        the decode program of every page-view bucket up to the longest
        request, then one tiny request through the batcher."""
        dec = self.decoder
        prompts, outputs = self.gen.multiset(self.traffic, n)
        buckets = sorted({dec.bucket_for(int(p)) for p in prompts})
        for b in buckets:
            kv1, _ = dec.prefill([0], bucket=b)
            dec.adopt_paged(kv1, [])
        top = dec.view_bucket(dec.pages_for(int(prompts.max()) +
                                            int(outputs.max())))
        zeros = np.zeros(dec.batch, np.int32)
        views = [pv for pv in dec.page_buckets if pv <= top]
        for pv in views:
            dec.decode_paged(np.zeros((dec.batch, pv), np.int32), zeros,
                             zeros)
        self.batcher.submit(np.zeros(int(prompts.min()), np.int32),
                            max_new_tokens=2).result(timeout_s=600.0)
        self.ctx.log(f"warm: prefill buckets {buckets}, page views {views}, "
                     f"{dec.compile_count} programs")

    # -- one window -----------------------------------------------------------
    def _window(self, seconds: float, rate=None, trace: bool = False) -> dict:
        from znicz_tpu.observe.trace import TRACER
        from znicz_tpu.serve.batcher import QueueFull

        ctx, traffic = self.ctx, self.traffic
        schedule = self.gen.generate(traffic, ctx.seed, seconds,
                                     int(self.cfg["vocab_size"]), rate)
        trace_from = float(traffic.get("trace_from_s", 3.0))
        trace_len = float(traffic.get("trace_seconds", 6.0))
        TRACER.clear()
        records = []
        t0 = time.perf_counter()
        for req in schedule:
            due = t0 + req["due_s"]
            if trace and not ctx.tracing and req["due_s"] >= trace_from:
                ctx.trace_start()
            elif trace and req["due_s"] >= trace_from + trace_len:
                ctx.trace_stop()
                break                 # a traced run stops offering here
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec = {"due": due, "prompt": req["prompt"],
                   "max_new": req["max_new"], "stream": None}
            try:
                rec["stream"] = self.batcher.submit(
                    req["prompt"], max_new_tokens=req["max_new"],
                    temperature=0.0)
            except QueueFull as exc:
                rec["error"] = repr(exc)
            rec["sent"] = time.perf_counter()
            records.append(rec)
        if trace and ctx.tracing:
            ctx.trace_stop()
        deadline = time.perf_counter() + float(traffic["drain_s"])
        for rec in records:
            stream = rec["stream"]
            if stream is None:
                continue
            while stream._terminal is None and \
                    time.perf_counter() < deadline:
                time.sleep(0.005)
            term = stream._terminal
            if term is None:
                rec["error"] = "not finished at the drain deadline"
                stream.cancel()
            elif "error" in term:
                rec["error"] = term["error"]
        t_end = time.perf_counter()
        spans = [e for e in TRACER.export_dict()["traceEvents"]
                 if e.get("ph") == "X" and e["name"].startswith("generate.")]
        requests = []
        for rec in records:
            stream = rec["stream"]
            stamps = list(getattr(stream, "bench_stamps", [])) \
                if stream is not None else []
            failed = "error" in rec or len(stamps) != rec["max_new"]
            requests.append({
                "due": rec["due"] - t0, "sent": rec["sent"] - t0,
                "stamps": [s - t0 for s in stamps],
                "prompt_len": int(rec["prompt"].size),
                "max_new": rec["max_new"], "failed": failed,
                "error": rec.get("error"),
                "rid": None if stream is None else stream.request_id,
                "prompt": rec["prompt"],
                "tokens": [] if stream is None else list(stream.tokens)})
        return {"kind": "serve", "requests": requests, "program_spans": spans,
                "slots": self.decoder.batch, "offered_s": seconds,
                "wall_s": t_end - t0}

    # -- after the window -----------------------------------------------------
    def _check(self, requests: list) -> tuple[bool, list]:
        """Reference logits over a seeded sample of finished requests,
        the longest among them."""
        done = [r for r in requests if not r["failed"]]
        if not done:
            return False, ["check served_logit_gap: no request finished "
                           "FAILED"]
        n = min(int(self.traffic["check_requests"]), len(done))
        longest = max(range(len(done)), key=lambda i: done[i]["prompt_len"] +
                      done[i]["max_new"])
        rng = np.random.default_rng([int(self.ctx.seed), 0xC4EC])
        picks = [longest] + [int(i) for i in rng.permutation(len(done))
                             if i != longest][:n - 1]
        seqs = [(done[i]["prompt"], done[i]["tokens"]) for i in picks]
        t0 = time.perf_counter()
        logits = self.ref.served_logits(self.ctx.seed, self.cfg, seqs)
        gaps = self.ref.logit_gaps(logits, [s[1] for s in seqs])
        self.ctx.log(f"reference: {len(seqs)} requests, {gaps.size} served "
                     f"tokens in {time.perf_counter() - t0:.1f} s; gap p50 "
                     f"{percentile(gaps, 50):.4g} p99 "
                     f"{percentile(gaps, 99):.4g}")
        self.readings = {"served_logit_gap": float(gaps.max())}
        if self.ctx.control:
            # the control need not decode: at each position of the same
            # prompts and tokens, the token the lower precision puts first
            low = self.ref.served_logits(self.ctx.seed, self.cfg, seqs,
                                         precision="fp8")
            cgaps = self.ref.logit_gaps(
                logits, [lg.argmax(axis=-1) for lg in low])
            self.control_readings = {"served_logit_gap": float(cgaps.max())}
        return judge({"served_logit_gap": (
            float(gaps.max()), f"{gaps.size} served tokens of {len(seqs)} "
            f"requests, longest {len(seqs[0][0])}+{len(seqs[0][1])}")},
            self.ref.LIMITS)

    def _shutdown(self) -> None:
        self.batcher.stop(drain=False, join_timeout_s=30.0)
        self.batcher = self.decoder = None
        gc.collect()

    def run(self) -> dict:
        ctx = self.ctx
        if ctx.chips != 1:
            raise BenchmarkError("lm_serve cells run on one chip")
        rates = ctx.sweep or [None]
        top_rate = max(r or self.traffic["rate_rps"] for r in rates)
        self._boot()
        with _Stamps():
            try:
                self._warm(self.gen.count(self.traffic, ctx.seconds,
                                          top_rate))
                if ctx.sweep:
                    for rate in ctx.sweep:
                        self._sweep_line(rate, self._window(ctx.seconds,
                                                            rate))
                    return {"sweep": True}
                ctx.open_window()
                samples = self._window(ctx.seconds, trace=ctx.trace)
                ctx.close_window()
            finally:
                self._shutdown()
        requests = samples["requests"]
        ok, lines = self._check(requests)
        for r in requests:
            del r["prompt"], r["tokens"]
        samples["readings"] = self.readings
        if ctx.control:
            samples["control_readings"] = self.control_readings
        failed = sum(r["failed"] for r in requests)
        lines.append(f"check every due request finished: {failed} failed of "
                     f"{len(requests)} {'ok' if not failed else 'FAILED'}")
        for r in requests:
            if r["error"]:
                lines.append(f"  failed request: {r['error']}")
                break
        return {"correct": ok and not failed, "lines": lines,
                "attempted": len(requests), "failed": failed,
                "samples": samples}

    def _sweep_line(self, rate: float, s: dict) -> None:
        """One line of the knee sweep: does the queue grow over the
        window at this rate?"""
        reqs = [r for r in s["requests"] if r["stamps"]]
        reqs.sort(key=lambda r: r["due"])
        third = max(1, len(reqs) // 3)
        wait = [1e3 * (r["stamps"][0] - r["due"]) for r in reqs]
        tokens = sum(len(r["stamps"]) for r in reqs)
        first = sum(wait[:third]) / third
        last = sum(wait[-third:]) / third
        failed = sum(r["failed"] for r in s["requests"])
        self.ctx.log(
            f"sweep rate {rate:g} rps: offered {len(s['requests'])} failed "
            f"{failed} tokens/s {tokens / s['wall_s']:.1f} ttft mean first "
            f"third {first:.0f} ms last third {last:.0f} ms (growth "
            f"{last - first:+.0f} ms) ttft p95 {percentile(wait, 95):.0f} ms "
            f"drain {s['wall_s'] - s['offered_s']:.1f} s")


def run(ctx) -> dict:
    return Cell(ctx).run()
