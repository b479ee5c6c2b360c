"""Paged KV arena + speculative decoding + Pallas flash-decode
(ISSUE 12): paged attention reads pinned equal to contiguous-buffer
reads over randomized page tables/lengths, the interpret-mode Pallas
kernel pinned against the jnp reference within the established 2e-5
band, speculative greedy decode token-identical to non-speculative
decode (and through it to the full-pass logits oracle), page-budget
admission/eviction, the orphan sweep, and the exact page ledger after
the chaos drill."""

import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from znicz_tpu.serve import (ArenaExhausted, ContinuousBatcher,
                             GenerateMetrics, GenerationError, KVDecoder,
                             PagedKVDecoder, PageLedger, truncate_draft)

N_LAYERS, D, HEADS, FF, VOCAB = 2, 32, 4, 64, 31


@pytest.fixture(scope="module")
def params():
    from znicz_tpu.parallel.params import init_params

    return init_params(np.random.default_rng(3), N_LAYERS, D, HEADS, FF,
                       VOCAB)


@pytest.fixture(scope="module")
def contiguous(params):
    return KVDecoder(params, heads=HEADS, max_len=32, batch=1)


@pytest.fixture(scope="module")
def paged_cache(params):
    """One paged decoder per config for the module — compiled programs
    are request-independent, so tests share the compile cost."""
    cache: dict = {}

    def get(batch=2, page=8, arena_pages=None, max_len=32,
            use_pallas=False):
        key = (batch, page, arena_pages, max_len, use_pallas)
        if key not in cache:
            cache[key] = PagedKVDecoder(
                params, heads=HEADS, max_len=max_len, batch=batch,
                page=page, arena_pages=arena_pages,
                use_pallas=use_pallas)
        return cache[key]

    return get


def _drive_paged(dec, prompt, n_new, slot=0, scramble_rng=None):
    """Hand-drive one request through the paged plane (greedy),
    returning its tokens.  ``scramble_rng`` churns the free list with
    random alloc/free cycles first, so the request lands on an
    arbitrary, non-contiguous, non-monotone page set — the property
    the page table must make invisible."""
    if scramble_rng is not None:
        held = dec.ledger.alloc(
            int(scramble_rng.integers(1, dec.ledger.free - 4)))
        keep = scramble_rng.permutation(len(held))
        dec.ledger.release([held[i] for i in keep])
    pages = dec.ledger.alloc(dec.pages_for(len(prompt)))
    kv1, logits = dec.prefill(prompt,
                              bucket=dec.bucket_for(len(prompt)))
    dec.adopt_paged(kv1, pages)
    pos, out = len(prompt), []
    tok = int(np.argmax(logits))
    out.append(tok)
    for _ in range(n_new - 1):
        while len(pages) * dec.page < pos + 1:
            pages.extend(dec.ledger.alloc(1))
        pt = np.zeros((dec.batch, dec.view_bucket(len(pages))),
                      np.int32)
        pt[slot, :len(pages)] = pages
        pos_v = np.zeros(dec.batch, np.int32)
        tok_v = np.zeros(dec.batch, np.int32)
        pos_v[slot], tok_v[slot] = pos, tok
        lg = dec.decode_paged(pt, pos_v, tok_v)
        tok = int(np.argmax(lg[slot]))
        out.append(tok)
        pos += 1
    dec.ledger.release(pages)
    return out


# -- the tentpole pin: paged reads == contiguous reads ------------------------

def test_paged_decode_matches_contiguous_over_random_page_tables(
        params, contiguous, paged_cache):
    """Property-style: randomized prompts/lengths decoded through
    scrambled (non-contiguous, reused) page tables must reproduce the
    contiguous-buffer decode token for token — page layout is invisible
    to the math."""
    dec = paged_cache(batch=2, page=8, arena_pages=17)
    rng = np.random.default_rng(11)
    for trial in range(6):
        p_len = int(rng.integers(1, 12))
        n_new = int(rng.integers(2, 32 - p_len))
        prompt = rng.integers(0, VOCAB, size=p_len).tolist()
        want = contiguous.generate(prompt, n_new)
        got = _drive_paged(dec, prompt, n_new,
                           slot=int(rng.integers(0, dec.batch)),
                           scramble_rng=rng)
        assert got == want, (trial, prompt, n_new)
    assert dec.ledger.used == 0         # every trial returned its pages


def test_paged_logits_match_contiguous_within_band(params, contiguous,
                                                   paged_cache):
    dec = paged_cache(batch=2, page=8, arena_pages=17)
    prompt = [5, 7, 1, 30, 12]
    kv, lg_c = contiguous.prefill(prompt, bucket=16)
    pages = dec.ledger.alloc(dec.pages_for(len(prompt)))
    kv1, lg_p = dec.prefill(prompt, bucket=8)
    dec.adopt_paged(kv1, pages)
    np.testing.assert_allclose(lg_p, lg_c, rtol=2e-5, atol=2e-5)
    pos, tok = len(prompt), int(np.argmax(lg_c))
    for _ in range(6):
        kv, bl = contiguous.decode(kv, [pos], [tok])
        while len(pages) * dec.page < pos + 1:
            pages.extend(dec.ledger.alloc(1))   # grow = page append
        pt = np.zeros((2, dec.view_bucket(len(pages))), np.int32)
        pt[0, :len(pages)] = pages
        pl_ = dec.decode_paged(pt, np.array([pos, 0], np.int32),
                               np.array([tok, 0], np.int32))
        np.testing.assert_allclose(pl_[0], bl[0], rtol=2e-5, atol=2e-5)
        tok = int(np.argmax(bl[0]))
        pos += 1
    dec.ledger.release(pages)


# -- Pallas flash-decode kernel ----------------------------------------------

def test_pallas_decode_kernel_interpret_matches_jnp_reference():
    from znicz_tpu.ops.pallas.decode import (paged_flash_decode,
                                             reference, supported)

    rng = np.random.default_rng(0)
    for B, H, Dh, page, n_pages, P in ((3, 4, 8, 8, 10, 2),
                                       (2, 2, 16, 4, 7, 4),
                                       (1, 1, 8, 16, 3, 1)):
        q = rng.normal(size=(B, H, Dh)).astype(np.float32)
        k = rng.normal(size=(n_pages, page, H, Dh)).astype(np.float32)
        v = rng.normal(size=(n_pages, page, H, Dh)).astype(np.float32)
        pt = rng.integers(0, n_pages, size=(B, P)).astype(np.int32)
        lengths = rng.integers(1, P * page + 1, size=(B,)) \
            .astype(np.int32)
        o = paged_flash_decode(q, k, v, pt, lengths, interpret=True)
        r = reference(q, k, v, pt, lengths)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)
    assert supported(8, 128) and not supported(7, 128) \
        and not supported(8, 64)
    with pytest.raises(ValueError, match="supported"):
        paged_flash_decode(q, k, v, pt, lengths, interpret=False)


def test_paged_decoder_with_pallas_kernel_matches_contiguous(
        params, contiguous, paged_cache):
    """The whole decode program with the kernel swapped in (interpret
    mode on CPU) still reproduces the contiguous greedy sequence and
    stays in the 2e-5 logits band."""
    dec = paged_cache(batch=1, page=8, arena_pages=9, use_pallas=True)
    prompt = [2, 9, 4, 17]
    want = contiguous.generate(prompt, 8)
    assert _drive_paged(dec, prompt, 8) == want


# -- speculative decoding -----------------------------------------------------

def test_speculative_greedy_token_identical_to_plain_decode(
        params, contiguous, paged_cache):
    """THE speculation pin: greedy decode with the draft+verify rounds
    is token-identical to non-speculative decode — and through PR 10's
    oracle pin, to the full-pass training forward."""
    target = paged_cache(batch=2, page=8, arena_pages=17)
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=32, batch=2, page=8)
    batcher = ContinuousBatcher(target, draft=draft, spec_k=3,
                                default_timeout_s=60.0)
    try:
        prompts = [[5, 7, 1, 30, 12], [2, 9], [1, 2, 3, 4], [8]]
        want = [contiguous.generate(p, 10) for p in prompts]
        got = [batcher.submit(p, max_new_tokens=10).result(timeout_s=60)
               for p in prompts]
        assert got == want
        snap = batcher.metrics.snapshot()
        # every greedy round judges exactly k draft tokens
        assert snap["spec_accepted"] + snap["spec_rejected"] > 0
        assert (snap["spec_accepted"] + snap["spec_rejected"]) % 3 == 0
    finally:
        batcher.stop()


def test_speculative_sampled_requests_keep_seeded_distribution(
        params, paged_cache):
    """A temperature>0 request rides the verify pass's position-0
    logits — its exact decode distribution — so seeded sampling
    reproduces across speculative runs AND matches the non-speculative
    batcher."""
    target = paged_cache(batch=2, page=8, arena_pages=17)
    plain = ContinuousBatcher(target)
    try:
        want = plain.submit([7, 8, 9], max_new_tokens=6,
                            temperature=0.9, top_k=5,
                            seed=42).result(timeout_s=60)
    finally:
        plain.stop()
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=32, batch=2, page=8)
    spec = ContinuousBatcher(target, draft=draft, spec_k=3)
    try:
        got = spec.submit([7, 8, 9], max_new_tokens=6, temperature=0.9,
                          top_k=5, seed=42).result(timeout_s=60)
    finally:
        spec.stop()
    assert got == want


def test_speculative_config_validation(params, paged_cache):
    target = paged_cache(batch=2, page=8, arena_pages=17)
    contig = KVDecoder(params, heads=HEADS, max_len=32, batch=2)
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=32, batch=2, page=8)
    with pytest.raises(ValueError, match="Paged"):
        ContinuousBatcher(contig, draft=draft)
    bad_batch = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                               max_len=32, batch=3, page=8)
    with pytest.raises(ValueError, match="batch"):
        ContinuousBatcher(target, draft=bad_batch)
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatcher(target, draft=draft, spec_k=0)
    with pytest.raises(ValueError, match="draft"):
        truncate_draft(params, N_LAYERS)        # not smaller


def test_speculative_request_to_the_max_len_boundary(params, contiguous):
    """Review regression: a request whose budget runs to the max_len
    boundary must not push the verify pass past the widest compiled
    page view (or past its own page budget) — rounds near the end
    degrade to plain decode instead, and the stream stays
    token-identical."""
    target = PagedKVDecoder(params, heads=HEADS, max_len=32, batch=2,
                            page=8, arena_pages=9)  # exactly 2x budget? 8 usable
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=32, batch=2, page=8)
    batcher = ContinuousBatcher(target, draft=draft, spec_k=4,
                                default_timeout_s=60.0)
    try:
        prompt = [5, 7, 1, 30]
        got = batcher.submit(prompt, max_new_tokens=28) \
            .result(timeout_s=60)           # budget 32 == max_len
        assert got == contiguous.generate(prompt, 28)
        assert batcher.page_ledger()["pages_used"] == 0
    finally:
        batcher.stop()


def test_speculative_warmup_with_page_smaller_than_round(params):
    """Review regression: warmup(spec_k) must skip page views too
    narrow to ever hold a verify round (page < spec_k + 1) instead of
    crashing the boot — live traffic can never dispatch them."""
    dec = PagedKVDecoder(params, heads=HEADS, max_len=16, batch=1,
                         page=4)
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=16, batch=1, page=4)
    dec.warmup(spec_k=4)
    draft.warmup()
    base = dec.compile_count + draft.compile_count
    batcher = ContinuousBatcher(dec, draft=draft, spec_k=4)
    try:
        assert len(batcher.submit([3, 1], max_new_tokens=10)
                   .result(timeout_s=60)) == 10
    finally:
        batcher.stop()
    assert dec.compile_count + draft.compile_count == base


def test_spec_counter_children_exist_at_boot(params, paged_cache):
    """Review regression: the init-time pre-touch must MATERIALIZE both
    spec counter series (a fleet delta rule needs the 0 baseline, not a
    missing key)."""
    from znicz_tpu.observe import REGISTRY

    target = paged_cache(batch=2, page=8, arena_pages=17)
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=32, batch=2, page=8)
    batcher = ContinuousBatcher(target, draft=draft, spec_k=3)
    try:
        prom = REGISTRY.render_prometheus()
        assert 'znicz_generate_spec_tokens_total{event="accepted"}' \
            in prom
        assert 'znicz_generate_spec_tokens_total{event="rejected"}' \
            in prom
    finally:
        batcher.stop()


# -- arena admission / eviction / ledger --------------------------------------

def test_zero_recompiles_paged_and_speculative_steady_state(params):
    target = PagedKVDecoder(params, heads=HEADS, max_len=16, batch=2,
                            page=8)
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=16, batch=2, page=8)
    target.warmup(spec_k=2)
    draft.warmup()
    base = target.compile_count + draft.compile_count
    batcher = ContinuousBatcher(target, draft=draft, spec_k=2)
    try:
        streams = [batcher.submit(list(range(1, 2 + i % 4)),
                                  max_new_tokens=3 + i % 5, seed=i)
                   for i in range(8)]
        for s in streams:
            assert len(s.result(timeout_s=60)) >= 3
    finally:
        batcher.stop()
    assert target.compile_count + draft.compile_count == base


def test_arena_backpressure_queues_until_pages_free(params):
    """Admission is gated on the PAGE budget, not the slot map: with
    arena room for only one live request's prompt, the second waits
    QUEUED (never failed) and runs once the first finishes and frees
    its pages."""
    dec = PagedKVDecoder(params, heads=HEADS, max_len=64, batch=2,
                         page=8, arena_pages=6)     # 5 usable pages
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0)
    try:
        # 24-token prompts need 3 pages at admission and 5 by the end
        # (budget 40) — two cannot be resident together in 5 pages
        a = batcher.submit([1] * 24, max_new_tokens=16)
        b = batcher.submit([2] * 24, max_new_tokens=16)
        assert len(a.result(timeout_s=60)) == 16
        assert len(b.result(timeout_s=60)) == 16
        assert b.first_token_step >= a.finish_step  # truly serialized
        snap = batcher.metrics.snapshot()
        assert snap["completed"] == 2 and snap["failed"] == 0
        assert snap["pages_used"] == 0 and snap["pages_total"] == 5
    finally:
        batcher.stop()


def test_never_servable_budget_names_arena(params):
    dec = PagedKVDecoder(params, heads=HEADS, max_len=32, batch=1,
                         page=8, arena_pages=4)     # 3 usable pages
    batcher = ContinuousBatcher(dec)
    try:
        # within max_len (32) but 4 pages > the 3 the arena holds:
        # rejected at submit, naming the arena (400, not a burned slot)
        with pytest.raises(ValueError, match="arena"):
            batcher.submit([1] * 8, max_new_tokens=24)
    finally:
        batcher.stop()


def test_mid_generation_exhaustion_evicts_grower_loudly(params):
    """When the arena runs dry mid-growth the GROWING request gets the
    error sentinel naming the arena, frees its pages, and everything
    else keeps decoding."""
    dec = PagedKVDecoder(params, heads=HEADS, max_len=32, batch=2,
                         page=8, arena_pages=5)     # 4 usable pages
    metrics = GenerateMetrics()
    batcher = ContinuousBatcher(dec, default_timeout_s=60.0,
                                metrics=metrics)
    try:
        # both admit at 1 page each; growth collides around row 8
        a = batcher.submit([1, 2], max_new_tokens=28)
        b = batcher.submit([3, 4], max_new_tokens=28)
        results = []
        for s in (a, b):
            try:
                results.append(("ok", len(s.result(timeout_s=60))))
            except GenerationError as exc:
                assert "arena exhausted" in str(exc)
                results.append(("evicted", len(s.tokens)))
        kinds = sorted(k for k, _ in results)
        assert kinds == ["evicted", "ok"], results
        # the survivor decoded its whole budget
        assert [n for k, n in results if k == "ok"] == [28]
        snap = metrics.snapshot()
        assert snap["completed"] == 1 and snap["failed"] == 1
        assert batcher.page_ledger()["pages_used"] == 0
    finally:
        batcher.stop()


def test_page_ledger_exact_after_chaos_drill(params):
    """Seeded ``generate.step`` crashes under concurrent paged+spec
    traffic: every admitted request still gets exactly one terminal
    event AND the arena page ledger closes — ``pages_used == Σ live
    slot pages`` (== 0 once drained), no orphaned pages."""
    from znicz_tpu.resilience import faults

    target = PagedKVDecoder(params, heads=HEADS, max_len=32, batch=2,
                            page=8, arena_pages=17)
    draft = PagedKVDecoder(truncate_draft(params, 1), heads=HEADS,
                           max_len=32, batch=2, page=8)
    metrics = GenerateMetrics()
    batcher = ContinuousBatcher(target, draft=draft, spec_k=2,
                                default_timeout_s=60.0, metrics=metrics)
    plan = faults.FaultPlan(seed=13)
    for hit in (3, 8):
        plan.crash_at("generate.step", at_hit=hit)
    outcomes: dict = {}
    lock = threading.Lock()

    def client(cid):
        stream = batcher.submit([1 + cid % 5, 2], max_new_tokens=6,
                                seed=cid)
        while True:
            event = stream.next_event(timeout=30)
            if event.get("done") or "error" in event:
                with lock:
                    outcomes[cid] = event
                return

    try:
        with faults.active(plan):
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert len(plan.log) == 2, plan.log
            # the worker survived and the arena still serves
            assert len(batcher.submit([1], max_new_tokens=3)
                       .result(timeout_s=30)) == 3
        led = batcher.page_ledger()
        assert led["pages_used"] == led["pages_owned"] == 0, led
        assert led.get("draft_pages_used") == 0, led
        snap = metrics.snapshot()
        assert snap["admitted"] == 7
        assert snap["admitted"] == snap["completed"] + snap["failed"] \
            + snap["abandoned"]
    finally:
        batcher.stop()


def test_page_ledger_primitives():
    led = PageLedger(5)
    assert led.total == 4 and led.free == 4
    pages = led.alloc(3)
    assert 0 not in pages and led.used == 3 and led.peak_used == 3
    with pytest.raises(ArenaExhausted):
        led.alloc(2)
    led.release(pages[:1])
    with pytest.raises(ValueError, match="double free"):
        led.release(pages[:1])
    assert led.reclaim(pages[1:2]) == 1     # pages[2] was orphaned
    assert led.used == 1
    with pytest.raises(ValueError):
        PageLedger(1)


def test_paged_decoder_validation(params, paged_cache):
    with pytest.raises(ValueError, match="arena_pages"):
        PagedKVDecoder(params, heads=HEADS, max_len=32, batch=1,
                       page=8, arena_pages=1)
    dec = paged_cache(batch=2, page=8, arena_pages=17)
    with pytest.raises(ValueError, match="page view"):
        dec.decode_paged(np.zeros((2, 1), np.int32),
                         np.array([8, 0], np.int32),
                         np.zeros(2, np.int32))    # row 8 of an 8-row view
    with pytest.raises(ValueError, match="bucket"):
        dec.decode_paged(np.zeros((2, 3), np.int32),
                         np.zeros(2, np.int32), np.zeros(2, np.int32))


# -- HTTP: over-limit prompt is a 400 naming the configured limit -------------

def test_http_over_limit_prompt_is_400_naming_max_len(params):
    import json
    import urllib.error
    import urllib.request

    from znicz_tpu.serve import GenerateServer

    charmap = list("abcdefghijklmnopqrstuvwxyz .,!?")
    dec = PagedKVDecoder(params, heads=HEADS, max_len=32, batch=2,
                         page=8)
    server = GenerateServer(ContinuousBatcher(dec), charmap=charmap)
    port = server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt": "a" * 40,
                             "max_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400
        msg = json.loads(err.value.read())["error"]
        # names the configured limit, not an opaque failure — and the
        # rejection happened at admission, before any prefill
        assert "max_len 32" in msg and "--max-len" in msg
        assert server.metrics.snapshot()["admitted"] == 0
        assert dec.prefill_count == 0
    finally:
        server.stop()


# -- draft export / load ------------------------------------------------------

def test_export_lm_draft_roundtrip(params, tmp_path):
    from znicz_tpu.utils.export import export_lm, load_lm, load_lm_draft

    path = str(tmp_path / "lm.npz")
    draft = truncate_draft(params, 1)
    export_lm(params, path, heads=HEADS,
              charmap=list("abcdefghijklmnopqrstuvwxyz .,!?"),
              name="tiny", draft_params=draft)
    p2, meta = load_lm(path)
    assert meta["draft"] == {"n_layers": 1, "d": D, "heads": HEADS,
                             "ff": FF, "vocab": VOCAB}
    # the target pytree is untouched by the draft riding along
    assert len(p2["blocks"]) == N_LAYERS
    np.testing.assert_array_equal(p2["emb"], params["emb"])
    d2, dmeta = load_lm_draft(path)
    assert dmeta["n_layers"] == 1 and len(d2["blocks"]) == 1
    np.testing.assert_array_equal(d2["blocks"][0]["w1"],
                                  params["blocks"][0]["w1"])
    # draft-less packages answer (None, None), not an error
    plain = str(tmp_path / "plain.npz")
    export_lm(params, plain, heads=HEADS)
    assert load_lm_draft(plain) == (None, None)


def test_units_export_lm_ships_truncated_draft(params, tmp_path):
    from znicz_tpu.units.lm import TransformerLMStep
    from znicz_tpu.utils.export import load_lm_draft

    class FakeLoader:
        vocab = list("abcdefghijklmnopqrstuvwxyz .,!?")
        vocab_size = VOCAB

    step = TransformerLMStep(loader=FakeLoader(), n_layers=N_LAYERS,
                             d=D, heads=HEADS, ff=FF)
    step._params = params
    path = step.export_lm(str(tmp_path / "lm.npz"), draft_layers=1)
    dparams, dmeta = load_lm_draft(path)
    assert dmeta["n_layers"] == 1 and dmeta["heads"] == HEADS
    np.testing.assert_array_equal(dparams["head"], params["head"])
