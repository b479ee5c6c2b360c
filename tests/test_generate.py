"""Generative serving plane (ISSUE 10): KV-cache incremental decode
pinned bit-equivalent to the training transformer's full forward pass,
bucketed zero-steady-state-recompile decode programs, seeded sampling,
the continuous batcher's slot lifecycle (late join, deadline, abort,
backpressure, exact terminal-event ledger), the kill-mid-decode chaos
drill, the streaming HTTP front end, the `python -m znicz_tpu generate`
CLI, and LM package export/load."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

pytest.importorskip("jax")

from znicz_tpu.serve import (ContinuousBatcher, GenerateMetrics,
                             GenerateServer, GenerationError, KVDecoder,
                             QueueFull, TokenSampler)

N_LAYERS, D, HEADS, FF, VOCAB = 2, 32, 4, 64, 31
CHARMAP = list("abcdefghijklmnopqrstuvwxyz .,!?")
assert len(CHARMAP) == VOCAB


@pytest.fixture(scope="module")
def params():
    from znicz_tpu.parallel.params import init_params

    return init_params(np.random.default_rng(3), N_LAYERS, D, HEADS, FF,
                       VOCAB)


@pytest.fixture(scope="module")
def decoder_cache(params):
    """One decoder per (batch, max_len) for the whole module — program
    caches are request-independent, so tests share the compile cost."""
    cache: dict = {}

    def get(batch: int = 1, max_len: int = 32) -> KVDecoder:
        key = (batch, max_len)
        if key not in cache:
            cache[key] = KVDecoder(params, heads=HEADS, max_len=max_len,
                                   batch=batch)
        return cache[key]

    return get


class _SlowDecoder:
    """Delegating proxy that stretches each decode step — deadline /
    abort / join tests need steps slow enough to act between."""

    def __init__(self, decoder: KVDecoder, delay_s: float) -> None:
        self._decoder = decoder
        self._delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._decoder, name)

    def decode(self, kv, pos, token):
        time.sleep(self._delay_s)
        return self._decoder.decode(kv, pos, token)


# -- sampling ----------------------------------------------------------------

def test_sampler_greedy_and_determinism():
    logits = np.array([0.1, 2.0, 0.3, 1.9], np.float32)
    assert TokenSampler(temperature=0.0).sample(logits) == 1
    assert TokenSampler(temperature=0.9, top_k=1).sample(logits) == 1
    a = [TokenSampler(seed=7, temperature=0.8, top_k=3).sample(logits)
         for _ in range(5)]
    b = [TokenSampler(seed=7, temperature=0.8, top_k=3).sample(logits)
         for _ in range(5)]
    assert a == b                       # fixed seed reproduces exactly
    s = TokenSampler(seed=1, temperature=1.0, top_k=2)
    draws = {s.sample(logits) for _ in range(50)}
    assert draws <= {1, 3}              # top-2 of the logits only
    with pytest.raises(ValueError):
        TokenSampler(temperature=-1.0)
    with pytest.raises(ValueError):
        TokenSampler(top_k=-2)


# -- the correctness anchor: KV decode == full forward passes ----------------

def test_greedy_kv_decode_matches_full_forward_oracle(params,
                                                      decoder_cache):
    """THE pin for the whole cache: greedy decode of N tokens through
    prefill + incremental decode must reproduce N full forward passes
    through the REAL training transformer (``make_logits_fn``, sharing
    ``_forward_hidden`` with the train/eval steps) token for token,
    with per-step logits matching to float32 rounding."""
    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.parallel.transformer import make_logits_fn

    mesh = make_mesh({"data": 1, "seq": 1, "model": 1})
    oracle = make_logits_fn(mesh, N_LAYERS, D, HEADS, FF, VOCAB)
    prompt = [5, 7, 1, 30, 12]
    n_new = 12

    toks = list(prompt)
    oracle_tokens, oracle_logits = [], []
    for _ in range(n_new):
        lg = np.asarray(oracle(params, np.asarray([toks], np.int32)))
        lg = lg[0, -1]
        t = int(np.argmax(lg))
        oracle_tokens.append(t)
        oracle_logits.append(lg)
        toks.append(t)

    dec = decoder_cache(batch=1, max_len=32)
    kv, logits = dec.prefill(prompt,
                             bucket=dec.bucket_for(len(prompt) + n_new))
    kv_tokens, kv_logits = [], []
    pos = len(prompt)
    for i in range(n_new):
        t = int(np.argmax(logits))
        kv_tokens.append(t)
        kv_logits.append(np.asarray(logits))
        if i + 1 < n_new:
            kv, batch_logits = dec.decode(kv, [pos], [t])
            logits = batch_logits[0]
            pos += 1

    # bit-identical decoded sequence — the generative correctness pin
    assert kv_tokens == oracle_tokens
    for lg_o, lg_k in zip(oracle_logits, kv_logits):
        np.testing.assert_allclose(lg_k, lg_o, rtol=2e-5, atol=2e-5)

    # generate() is the same path end to end
    out = dec.generate(prompt, n_new, TokenSampler(temperature=0.0))
    assert out == oracle_tokens


def test_prefill_last_position_logits_match_oracle(params,
                                                   decoder_cache):
    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.parallel.transformer import make_logits_fn

    mesh = make_mesh({"data": 1, "seq": 1, "model": 1})
    oracle = make_logits_fn(mesh, N_LAYERS, D, HEADS, FF, VOCAB)
    prompt = [2, 9, 4, 17, 8, 23, 1]
    lg_o = np.asarray(oracle(params,
                             np.asarray([prompt], np.int32)))[0, -1]
    _, lg_k = decoder_cache(batch=1, max_len=32).prefill(prompt)
    np.testing.assert_allclose(lg_k, lg_o, rtol=2e-5, atol=2e-5)


# -- bucket policy / compile accounting --------------------------------------

def test_zero_recompiles_across_mixed_lengths_within_bucket(params):
    dec = KVDecoder(params, heads=HEADS, max_len=16, batch=1)
    dec.generate([1, 2, 3], 9)          # lands in bucket 16, compiles
    base = dec.compile_count
    for prompt_len, n_new in ((2, 10), (5, 11), (7, 9), (1, 12)):
        dec.generate(list(range(1, prompt_len + 1)), n_new)
    assert dec.compile_count == base    # mixed lengths, zero recompiles


def test_warmup_compiles_every_bucket_once(params):
    dec = KVDecoder(params, heads=HEADS, max_len=8, batch=2)
    n = dec.warmup()
    # prefill + decode + adopt per bucket (1, 2, 4, 8)
    assert n == dec.compile_count == 3 * len(dec.buckets)
    assert dec.warmup() == n            # idempotent: nothing recompiles


def test_decode_past_cache_bucket_raises_not_corrupts(params,
                                                      decoder_cache):
    dec = decoder_cache(batch=1, max_len=32)
    kv, _ = dec.prefill([1, 2, 3], bucket=4)
    with pytest.raises(ValueError, match="outside cache bucket"):
        dec.decode(kv, [4], [0])        # row 4 of a 4-row cache


def test_grow_preserves_generation(params, decoder_cache):
    """A cache grown mid-request decodes the same tokens as one
    allocated at the big bucket from the start (padding is masked)."""
    dec = decoder_cache(batch=1, max_len=32)
    prompt = [3, 1, 4, 1, 5]
    straight = dec.generate(prompt, 8)  # bucket 16 from the start
    kv, logits = dec.prefill(prompt, bucket=8)
    grown_tokens, pos = [], len(prompt)
    kv = dec.grow(kv, 16)
    for i in range(8):
        t = int(np.argmax(logits))
        grown_tokens.append(t)
        if i + 1 < 8:
            kv, bl = dec.decode(kv, [pos], [t])
            logits = bl[0]
            pos += 1
    assert grown_tokens == straight


def test_decoder_refuses_moe_and_bad_input(params):
    moe = {"emb": params["emb"], "head": params["head"],
           "blocks": [{**params["blocks"][0], "ew1": np.zeros((2, D, FF)),
                       "eb1": np.zeros((2, FF))}]}   # dense-masked: biased
    with pytest.raises(NotImplementedError, match="MoE"):
        KVDecoder(moe, heads=HEADS, max_len=8)
    dec = KVDecoder(params, heads=HEADS, max_len=8, batch=1)
    with pytest.raises(ValueError, match="token ids"):
        dec.prefill([VOCAB + 5])
    with pytest.raises(ValueError, match="max_len"):
        dec.bucket_for(9)
    with pytest.raises(ValueError, match="empty"):
        dec.prefill([])


# -- continuous batching -----------------------------------------------------

def test_late_request_joins_running_batch_without_drain(params,
                                                        decoder_cache):
    """ISSUE acceptance: a request arriving mid-generation joins the
    running decode batch at the next step and finishes while the
    earlier long request is still decoding — pinned on the batcher's
    step counter, not wall clock."""
    dec = decoder_cache(batch=3, max_len=64)
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0)
    try:
        long_req = batcher.submit([1, 2, 3], max_new_tokens=40)
        while batcher.step_count < 5:
            time.sleep(0.005)
        late = batcher.submit([4, 5], max_new_tokens=4)
        late_tokens = late.result(timeout_s=60)
        long_tokens = long_req.result(timeout_s=60)
        assert len(late_tokens) == 4 and len(long_tokens) == 40
        # the late joiner entered AFTER the long request started and
        # finished BEFORE it — continuous, not drain-per-batch
        assert late.first_token_step >= 5
        assert late.finish_step < long_req.finish_step
        # TTFT is steps-not-drain: far fewer steps than the long run
        assert late.finish_step - late.first_token_step <= 4
        snap = batcher.metrics.snapshot()
        assert snap["admitted"] == snap["completed"] == 2
        assert snap["ttft"]["count"] == 2
    finally:
        batcher.stop()


def test_steady_state_continuous_traffic_zero_recompiles(params):
    dec = KVDecoder(params, heads=HEADS, max_len=16, batch=2)
    dec.warmup()
    base = dec.compile_count
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0)
    try:
        streams = [batcher.submit(list(range(1, 2 + i % 4)),
                                  max_new_tokens=3 + i % 5, seed=i,
                                  temperature=0.5, top_k=4)
                   for i in range(8)]
        for s in streams:
            assert len(s.result(timeout_s=60)) >= 3
    finally:
        batcher.stop()
    assert dec.compile_count == base    # warmed buckets, mixed lengths


def test_seeded_generation_reproduces_across_batcher_runs(params,
                                                          decoder_cache):
    dec = decoder_cache(batch=2, max_len=32)
    out = []
    for _ in range(2):
        batcher = ContinuousBatcher(dec)
        try:
            out.append(batcher.submit(
                [7, 8, 9], max_new_tokens=6, temperature=0.9, top_k=5,
                seed=42).result(timeout_s=60))
        finally:
            batcher.stop()
    assert out[0] == out[1]


def test_deadline_mid_generation_gets_error_sentinel(params,
                                                     decoder_cache):
    dec = _SlowDecoder(decoder_cache(batch=2, max_len=64), 0.01)
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0)
    try:
        s = batcher.submit([1] * 4, max_new_tokens=60, timeout_s=0.08)
        with pytest.raises(GenerationError, match="deadline"):
            s.result(timeout_s=30)
        assert 0 < len(s.tokens) < 60   # partial stream, then sentinel
        snap = batcher.metrics.snapshot()
        assert snap["failed"] == 1 and snap["completed"] == 0
    finally:
        batcher.stop()


def test_cancel_frees_slot_and_counts_abandoned(params, decoder_cache):
    dec = _SlowDecoder(decoder_cache(batch=2, max_len=64), 0.01)
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0)
    try:
        s = batcher.submit([2] * 4, max_new_tokens=60)
        time.sleep(0.05)
        s.cancel()
        tokens = s.result(timeout_s=30)     # "done"/aborted, not error
        assert 0 < len(tokens) < 60
        snap = batcher.metrics.snapshot()
        assert snap["abandoned"] == 1
        # slot is free again: a follow-up request completes
        assert len(batcher.submit([1, 2], max_new_tokens=3)
                   .result(timeout_s=30)) == 3
    finally:
        batcher.stop()


def test_backpressure_and_never_admissible(params, decoder_cache):
    dec = _SlowDecoder(decoder_cache(batch=1, max_len=32), 0.02)
    batcher = ContinuousBatcher(dec, max_queue=1,
                                default_timeout_s=60.0)
    try:
        running = batcher.submit([1, 2], max_new_tokens=30)
        time.sleep(0.05)                # occupies the only slot
        queued = batcher.submit([3, 4], max_new_tokens=2)
        with pytest.raises(QueueFull):
            batcher.submit([5, 6], max_new_tokens=2)
        assert batcher.metrics.snapshot()["rejected"] == 1
        # over-budget request is bad input (400), not backpressure
        with pytest.raises(ValueError, match="max_len"):
            batcher.submit([1] * 10, max_new_tokens=30)
        assert len(running.result(60)) == 30
        assert len(queued.result(60)) == 2
    finally:
        batcher.stop()


def test_stop_drain_services_everything_admitted(params, decoder_cache):
    dec = decoder_cache(batch=2, max_len=32)
    batcher = ContinuousBatcher(dec)
    streams = [batcher.submit([1 + i], max_new_tokens=8)
               for i in range(5)]
    assert batcher.stop(drain=True)
    for s in streams:
        assert len(s.result(timeout_s=1)) == 8
    with pytest.raises(QueueFull):
        batcher.submit([1], max_new_tokens=2)


def test_stop_without_drain_fails_loudly(params, decoder_cache):
    dec = _SlowDecoder(decoder_cache(batch=1, max_len=32), 0.02)
    batcher = ContinuousBatcher(dec)
    active = batcher.submit([1, 2], max_new_tokens=25)
    time.sleep(0.05)
    queued = batcher.submit([3], max_new_tokens=4)
    assert batcher.stop(drain=False)
    for s in (active, queued):
        with pytest.raises(GenerationError, match="shut down"):
            s.result(timeout_s=1)


# -- chaos: kill mid-decode (ISSUE satellite) --------------------------------

def test_chaos_kill_mid_decode_exactly_one_terminal_per_request(
        params, decoder_cache):
    """Seeded ``generate.step`` crashes mid-decode: every admitted
    request still gets EXACTLY ONE terminal event (tokens then an error
    sentinel, or a clean end) — never silence, never a duplicate — the
    worker survives, and the ledger closes with ``==``."""
    from znicz_tpu.resilience import faults

    dec = decoder_cache(batch=2, max_len=32)
    metrics = GenerateMetrics()
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0,
                                metrics=metrics)
    plan = faults.FaultPlan(seed=13)
    for hit in (3, 8):                  # two seeded mid-decode kills
        plan.crash_at("generate.step", at_hit=hit)
    outcomes: dict = {}
    lock = threading.Lock()

    def client(cid):
        stream = batcher.submit([1 + cid % 5, 2], max_new_tokens=6,
                                seed=cid)
        terminal = None
        n_events = 0
        while True:
            event = stream.next_event(timeout=30)   # raises on silence
            n_events += 1
            if event.get("done") or "error" in event:
                terminal = event
                break
            assert n_events < 100       # a stream must terminate
        with lock:
            assert cid not in outcomes  # exactly one terminal observed
            outcomes[cid] = terminal

    try:
        with faults.active(plan):
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert len(plan.log) == 2, plan.log     # both kills fired
            # the worker survived: fresh traffic still serves
            assert len(batcher.submit([1], max_new_tokens=3)
                       .result(timeout_s=30)) == 3
    finally:
        batcher.stop()
    assert len(outcomes) == 6
    errs = [o for o in outcomes.values() if "error" in o]
    oks = [o for o in outcomes.values() if o.get("done") and
           "error" not in o]
    assert len(errs) >= 1 and len(oks) >= 1
    snap = metrics.snapshot()
    # exact ledger — every admitted request reached one terminal state
    assert snap["admitted"] == 7
    assert snap["admitted"] == snap["completed"] + snap["failed"] + \
        snap["abandoned"]
    assert snap["failed"] == len(errs)


# -- HTTP front end ----------------------------------------------------------

@pytest.fixture()
def gen_server(params, decoder_cache):
    dec = decoder_cache(batch=2, max_len=32)
    server = GenerateServer(ContinuousBatcher(dec), charmap=CHARMAP,
                            name="tiny")
    port = server.start()
    yield server, f"http://127.0.0.1:{port}"
    server.stop()


def _post(url, doc, timeout=30):
    return urllib.request.urlopen(urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"}), timeout=timeout)


def test_generate_http_streams_ndjson_with_terminal_line(gen_server):
    server, base = gen_server
    with _post(f"{base}/generate", {"prompt": "hi", "max_tokens": 6,
                                    "temperature": 0.7, "top_k": 5,
                                    "seed": 3}) as r:
        assert r.headers["Content-Type"] == "application/x-ndjson"
        lines = [json.loads(raw) for raw in r]
    assert len(lines) == 7
    assert all("token" in ln and "text" in ln for ln in lines[:-1])
    assert lines[-1] == {"done": True, "reason": "length",
                         "n_tokens": 6}
    # non-stream mode returns the identical seeded generation
    with _post(f"{base}/generate", {"prompt": "hi", "max_tokens": 6,
                                    "temperature": 0.7, "top_k": 5,
                                    "seed": 3, "stream": False}) as r:
        doc = json.loads(r.read())
    assert doc["tokens"] == [ln["token"] for ln in lines[:-1]]
    assert doc["text"] == "".join(ln["text"] for ln in lines[:-1])
    snap = json.loads(urllib.request.urlopen(f"{base}/metrics",
                                             timeout=10).read())
    assert snap["generate"]["completed"] == 2
    assert snap["generate"]["tokens"] == 12
    assert snap["decoder"]["vocab"] == VOCAB
    prom = urllib.request.urlopen(f"{base}/metrics.prom",
                                  timeout=10).read().decode()
    assert "znicz_generate_tokens_total" in prom
    assert "znicz_generate_ttft_seconds" in prom


def test_generate_http_rejects_bad_input(gen_server):
    _, base = gen_server
    for doc, match in (({"max_tokens": 4}, "prompt"),
                       ({"prompt": "ü"}, "vocab"),
                       ({"tokens": [999]}, "token ids"),
                       ({"prompt": "hi", "max_tokens": 0}, "max_new")):
        try:
            _post(f"{base}/generate", doc)
            raise AssertionError(f"{doc} accepted")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
            assert match in json.loads(exc.read())["error"]
    try:
        _post(f"{base}/nope", {"prompt": "hi"})
        raise AssertionError("bad path accepted")
    except urllib.error.HTTPError as exc:
        assert exc.code == 404
    meta = json.loads(urllib.request.urlopen(base, timeout=10).read())
    assert meta["model"]["kind"] == "lm" and meta["slots"] == 2


def test_generate_http_draining_healthz_and_503(params, decoder_cache):
    dec = _SlowDecoder(decoder_cache(batch=1, max_len=32), 0.02)
    server = GenerateServer(ContinuousBatcher(dec), charmap=CHARMAP)
    port = server.start()
    base = f"http://127.0.0.1:{port}"
    try:
        stream = server.batcher.submit([1, 2], max_new_tokens=25)
        stopper = threading.Thread(target=server.stop)
        stopper.start()
        time.sleep(0.1)                 # stop() blocked in the drain
        try:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
            raise AssertionError("healthz should be 503 during drain")
        except urllib.error.HTTPError as exc:
            assert exc.code == 503
            assert json.loads(exc.read())["status"] == "draining"
        stopper.join(timeout=60)
        assert not stopper.is_alive()
        assert len(stream.result(timeout_s=1)) == 25    # drained
    finally:
        server.stop()


# -- LM package export / load ------------------------------------------------

def test_export_lm_roundtrip_and_validation(params, tmp_path):
    from znicz_tpu.utils.export import export_lm, load_lm

    path = str(tmp_path / "lm.npz")
    export_lm(params, path, heads=HEADS, charmap=CHARMAP, name="tiny")
    p2, meta = load_lm(path)
    assert meta["format"] == "znicz_tpu.lm/1"
    assert (meta["n_layers"], meta["d"], meta["heads"], meta["ff"],
            meta["vocab"]) == (N_LAYERS, D, HEADS, FF, VOCAB)
    assert meta["charmap"] == CHARMAP
    np.testing.assert_array_equal(p2["emb"], params["emb"])
    np.testing.assert_array_equal(p2["blocks"][1]["w2"],
                                  params["blocks"][1]["w2"])
    with pytest.raises(ValueError, match="heads"):
        export_lm(params, str(tmp_path / "bad.npz"), heads=5)
    with pytest.raises(ValueError, match="charmap"):
        export_lm(params, str(tmp_path / "bad.npz"), heads=HEADS,
                  charmap=["a"])
    # a forward package is not an LM package — loud, typed refusal
    np.savez(str(tmp_path / "fwd.npz"), __arch__=np.array("{}"))
    with pytest.raises(ValueError, match="not an LM package"):
        load_lm(str(tmp_path / "fwd.npz"))


def test_transformer_lm_step_export_hook(params, tmp_path):
    """The units-layer handoff: an initialized TransformerLMStep
    packages its live params + the loader's charmap."""
    from znicz_tpu.units.lm import TransformerLMStep
    from znicz_tpu.utils.export import load_lm

    class FakeLoader:
        vocab = CHARMAP
        vocab_size = VOCAB

    step = TransformerLMStep(loader=FakeLoader(), n_layers=N_LAYERS,
                             d=D, heads=HEADS, ff=FF)
    with pytest.raises(ValueError, match="initialized"):
        step.export_lm(str(tmp_path / "lm.npz"))
    step._params = params
    path = step.export_lm(str(tmp_path / "lm.npz"))
    p2, meta = load_lm(path)
    assert meta["charmap"] == CHARMAP and meta["heads"] == HEADS
    np.testing.assert_array_equal(p2["head"], params["head"])


def test_char_lm_run_exports_lm_package_when_configured(tmp_path):
    """models/char_lm.py's post-run epilogue: with
    root.common.engine.lm_export set, the trained step's params land as
    an LM package (and without it, nothing is written)."""
    from znicz_tpu.core.config import root
    from znicz_tpu.models import char_lm

    calls = []

    class FakeStep:
        def export_lm(self, path):
            calls.append(path)
            return path

    class FakeWorkflow:
        step = FakeStep()

    def load(builder, **kw):
        assert builder is char_lm.build
        return FakeWorkflow(), False

    target = str(tmp_path / "out.npz")
    old = root.common.engine.get("lm_export", "")
    try:
        root.common.engine.lm_export = ""
        char_lm.run(load, lambda: None)
        assert calls == []
        root.common.engine.lm_export = target
        char_lm.run(load, lambda: None)
        assert calls == [target]
    finally:
        root.common.engine.lm_export = old


# -- CLI ---------------------------------------------------------------------

def test_cli_generate_oneshot(params, tmp_path, capsys):
    from znicz_tpu.__main__ import main as cli_main
    from znicz_tpu.utils.export import export_lm

    pkg = str(tmp_path / "lm.npz")
    export_lm(params, pkg, heads=HEADS, charmap=CHARMAP)
    rc = cli_main(["generate", pkg, "--prompt", "hello",
                   "--max-tokens", "8", "--max-len", "32"])
    out = capsys.readouterr()
    assert rc == 0
    # eight streamed characters plus the closing newline (the charmap
    # has no newline, so the count is exact even for spaces)
    assert len(out.out) == 9 and out.out.endswith("\n")
    stats = json.loads(out.err.strip().splitlines()[-1])
    assert stats["n_tokens"] == 8 and stats["prompt_tokens"] == 5
    # deterministic greedy: a second run prints the same text
    cli_main(["generate", pkg, "--prompt", "hello", "--max-tokens", "8",
              "--max-len", "32"])
    assert capsys.readouterr().out == out.out


def test_cli_generate_rejects_bad_package(tmp_path, capsys):
    from znicz_tpu.__main__ import main as cli_main

    assert cli_main(["generate", "/nonexistent/lm.npz",
                     "--prompt", "x"]) == 2
    assert "cannot load" in capsys.readouterr().out
    np.savez(str(tmp_path / "fwd.npz"), __arch__=np.array("{}"))
    assert cli_main(["generate", str(tmp_path / "fwd.npz"),
                     "--prompt", "x"]) == 2
    assert "not an LM package" in capsys.readouterr().out
