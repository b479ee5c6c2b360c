"""Block-paged KV arena — the memory plane of generative serving
(ISSUE 12).

The contiguous :class:`~znicz_tpu.serve.kvcache.KVDecoder` cache
reserves one power-of-two bucket strip per slot and pays an O(bucket)
device copy every time the shared buffer grows.  This module replaces
that with the vLLM-shaped alternative: ONE preallocated device buffer of
fixed-size pages ``(layers, n_pages, page, heads, head_dim)`` shared by
every slot, plus a host-side per-slot page table.  A long-tail request
stops reserving worst-case memory (it holds exactly the pages its
resident tokens span), ``grow`` becomes a page-table append instead of a
device copy, and the slot ceiling is set by tokens actually resident —
not ``slots × max_bucket``.

Layout and invariants:

- **page 0 is scratch** — a reserved /dev/null page.  Page-table
  padding entries, writes from empty batch slots, and the tail of an
  adopt scatter all land there; its content is garbage by contract and
  no live view ever exposes it unmasked.  The allocator hands out pages
  ``1..n_pages-1`` only.
- A slot's page table maps sequence rows ``[0, len(pages)·page)`` to
  arena pages; row ``r`` lives at ``(pages[r // page], r % page)``.
- Compiled-shape policy mirrors the bucket discipline everywhere else
  in the serve plane: decode/verify programs are keyed on the
  power-of-two *page-view width* (``view_bucket``), so steady-state
  traffic over mixed lengths recompiles nothing and ``compile_count``
  stays assertable.
- Pages freed by a finished request may be reissued immediately: the
  new owner's rows are either rewritten before exposure or masked by
  its own ``pos`` (the same stale-row argument the contiguous cache
  makes for re-adopted slots, per page instead of per strip).

The attention math is inherited from :class:`KVDecoder` (the SAME
layer-norm / mask constants / f32 online-softmax recipe the training
forward uses), so the paged path stays pinned against the full-pass
logits oracle through the contiguous reference: paged reads over
randomized page tables must equal contiguous-buffer reads
(tests/test_paged.py).  The single-query hot path can optionally run
the Pallas flash-decode kernel (``ops/pallas/decode.py``), which
gathers K/V through the page table inside the kernel.
"""

from __future__ import annotations

import threading

import numpy as np

from znicz_tpu.serve.engine import bucket_sizes
from znicz_tpu.serve.kvcache import KVDecoder


class ArenaExhausted(RuntimeError):
    """No free pages left in the shared KV arena.  At admission this is
    backpressure (the batcher leaves the request queued); mid-generation
    it is the eviction policy — the growing request fails loudly with an
    error sentinel naming the arena."""


class PageLedger:
    """Host-side page accounting for one arena: free list, usage
    counters and the orphan sweep.  Page 0 (scratch) is never issued.

    Thread-safe, though in steady state only the continuous batcher's
    worker thread allocates and frees; ``submit`` threads read the
    counters for the never-servable check.
    """

    def __init__(self, n_pages: int) -> None:
        if n_pages < 2:
            raise ValueError(f"arena needs >= 2 pages (page 0 is the "
                             f"reserved scratch page), got {n_pages}")
        self.n_pages = int(n_pages)
        # pop() order hands out low page ids first — determinism for the
        # property tests, irrelevant to correctness
        self._free = list(range(self.n_pages - 1, 0, -1))
        self._lock = threading.Lock()
        self.peak_used = 0

    @property
    def total(self) -> int:
        """Allocatable pages (scratch excluded)."""
        return self.n_pages - 1

    @property
    def used(self) -> int:
        with self._lock:
            return self.total - len(self._free)

    @property
    def free(self) -> int:
        with self._lock:
            return len(self._free)

    def alloc(self, n: int) -> list:
        """Take ``n`` pages or raise :class:`ArenaExhausted` (all-or-
        nothing — a partial grant would orphan pages on the error
        path)."""
        with self._lock:
            if n > len(self._free):
                raise ArenaExhausted(
                    f"KV arena exhausted: need {n} pages, "
                    f"{len(self._free)} of {self.total} free")
            pages = [self._free.pop() for _ in range(n)]
            self.peak_used = max(self.peak_used,
                                 self.total - len(self._free))
            return pages

    def release(self, pages) -> None:
        with self._lock:
            free = set(self._free)
            for p in pages:
                p = int(p)
                if p <= 0 or p >= self.n_pages or p in free:
                    raise ValueError(f"release of page {p} not owned by "
                                     f"this ledger (double free?)")
                free.add(p)
                self._free.append(p)

    def reclaim(self, owned) -> int:
        """Orphan sweep (the PR 9 pid-unique-temp pattern, per page):
        free every used page NOT in ``owned`` — called after a crash
        path that may have lost a request between allocation and its
        page-table record.  Returns the number of pages reclaimed."""
        owned = {int(p) for p in owned}
        with self._lock:
            known = set(self._free) | owned
            orphans = [p for p in range(1, self.n_pages)
                       if p not in known]
            self._free.extend(orphans)
            return len(orphans)


class PagedKVDecoder(KVDecoder):
    """Bucketed incremental decoder over a shared block-paged KV arena.

    Extends :class:`KVDecoder` (prompt prefill, bucket policy, compile
    accounting and the single-request contiguous path are inherited)
    with the paged device plane:

    - ``adopt_paged(kv1, pages)`` — scatter a prefilled contiguous
      single-request cache into arena pages (admission);
    - ``decode_paged(page_table, pos, token)`` — one batched
      single-token step: write each slot's row through its page table,
      attend over the gathered page view;
    - ``verify_paged(page_table, pos, tokens)`` — the speculative
      target pass: write+attend ``q_len`` rows per slot in ONE
      dispatch, returning logits at every position (the acceptance
      harness feeds these straight to the greedy rule).

    ``page`` is the rows-per-page granularity; ``arena_pages`` sizes the
    shared buffer (default: worst case — every slot at ``max_len`` —
    plus the scratch page, so an unconfigured decoder can never lose to
    the contiguous layout; production sets it smaller and banks on the
    long tail).  ``use_pallas=True`` routes single-query decode
    attention through the Pallas flash-decode kernel (interpret mode on
    CPU; on a TPU a geometry the kernel cannot compile is a
    ``ValueError`` here) — OFF by default so the oracle pin rides one
    code path.
    """

    paged = True

    def __init__(self, params, heads: int, max_len: int = 256,
                 batch: int = 1, page: int = 16,
                 arena_pages: int | None = None,
                 use_pallas: bool = False) -> None:
        super().__init__(params, heads=heads, max_len=max_len,
                         batch=batch)
        self.page = int(page)
        if self.page < 1:
            raise ValueError(f"page must be >= 1, got {page}")
        self.max_pages = -(-self.max_len // self.page)
        self.page_buckets = bucket_sizes(self.max_pages)
        if arena_pages is None:
            arena_pages = self.batch * self.max_pages + 1
        self.arena_pages = int(arena_pages)
        if self.arena_pages < 2:
            raise ValueError(f"arena_pages={arena_pages}: need >= 2 "
                             f"(page 0 is the reserved scratch page)")
        self.ledger = PageLedger(self.arena_pages)
        self.use_pallas = bool(use_pallas)
        self._pdecode: dict = {}
        self._pverify: dict = {}
        self._padopt: dict = {}
        import jax
        import jax.numpy as jnp

        #: compiled Pallas needs TPU-tileable shapes; on every other
        #: backend the kernel runs interpreted (bit-for-bit the same
        #: recipe, minus the speed)
        self._pallas_interpret = jax.default_backend() != "tpu"
        if self.use_pallas and not self._pallas_interpret:
            from znicz_tpu.ops.pallas import decode as _pdk

            if not _pdk.supported(self.page, self.head_dim):
                # decide at CONSTRUCTION, not mid-request — and refuse:
                # the caller asked for the kernel, so serving the jnp
                # path instead would answer a different question
                raise ValueError(
                    f"pallas decode cannot compile page={self.page}, "
                    f"head_dim={self.head_dim} (need page % 8 == 0, "
                    f"head_dim % 128 == 0); drop --pallas-decode or "
                    f"change --page-size")
        dt = self._cast_policy()
        shape = (self.n_layers, self.arena_pages, self.page, self.heads,
                 self.head_dim)
        #: THE shared device arena — one buffer for every slot
        self._arena = {"k": jnp.zeros(shape, dt),
                       "v": jnp.zeros(shape, dt)}

    # -- page geometry -------------------------------------------------------
    def pages_for(self, n_rows: int) -> int:
        """Pages needed to hold ``n_rows`` sequence rows (min 1)."""
        return max(1, -(-int(n_rows) // self.page))

    def view_bucket(self, n_pages: int) -> int:
        """Smallest compiled page-view width covering ``n_pages``."""
        for b in self.page_buckets:
            if n_pages <= b:
                return b
        raise ValueError(f"{n_pages} pages > max_pages {self.max_pages} "
                         f"(max_len {self.max_len}, page {self.page})")

    def arena_bytes(self) -> int:
        """Device bytes held by the shared arena (both K and V)."""
        return int(self._arena["k"].nbytes + self._arena["v"].nbytes)

    # -- compiled program builders ------------------------------------------
    def _build_padopt(self, t_p: int):
        import jax
        import jax.numpy as jnp

        page = self.page
        n = self.pages_for(t_p)
        pad = n * page - t_p

        def adopt(kv, kv1, pages):
            out = {}
            for name in ("k", "v"):
                c1 = kv1[name]                   # (L, 1, t_p, H, Dh)
                if pad:
                    c1 = jnp.pad(c1, ((0, 0), (0, 0), (0, pad), (0, 0),
                                      (0, 0)))
                c1 = c1.reshape(self.n_layers, n, page, self.heads,
                                self.head_dim)
                # chunks beyond the request's owned pages carry masked
                # bucket padding; their `pages` entries are scratch
                out[name] = kv[name].at[:, pages].set(c1)
            return out

        # donate the arena (arg 0) so the splice is in-place off-CPU
        return jax.jit(adopt, donate_argnums=(0,) if self._donate
                       else ())

    def _paged_attend(self, jnp, q, ka, va, pt, pos):
        """Single-query attention over the gathered page view — q
        ``(B, 1, H, Dh)``, arena layer ``ka/va (N, page, H, Dh)``,
        ``pt (B, P)``, ``pos (B,)``; rows past each slot's ``pos`` (and
        every scratch-padding page) are masked with the shared -1e30
        constant, exactly like the contiguous decode."""
        B = q.shape[0]
        t_view = pt.shape[1] * self.page
        kc = ka[pt].reshape(B, t_view, self.heads, self.head_dim)
        vc = va[pt].reshape(B, t_view, self.heads, self.head_dim)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(self.head_dim).astype(s.dtype)
        kpos = jnp.arange(t_view)
        dead = kpos[None, :] > pos[:, None]
        s = jnp.where(dead[:, None, None, :],
                      jnp.asarray(-1e30, s.dtype), s)
        return self._attend(jnp, s, vc).reshape(B, 1, -1)

    def _build_pdecode(self, p_view: int):
        import jax
        import jax.numpy as jnp

        from znicz_tpu.parallel.blocks import _layer_norm

        H, Dh, page = self.heads, self.head_dim, self.page
        cdt = self._cast_policy()
        use_pallas = self.use_pallas
        interp = self._pallas_interpret

        def decode(params, kv, pt, pos, token):
            ps = jax.tree.map(lambda w: w.astype(cdt), params)
            B = token.shape[0]
            x = ps["emb"][token][:, None, :]         # (B, 1, d)
            pg_w = jnp.take_along_axis(pt, (pos // page)[:, None],
                                       axis=1)[:, 0]
            off = pos % page
            for li, p in enumerate(ps["blocks"]):
                h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
                q = (h @ p["wq"]).reshape(B, 1, H, Dh)
                k1 = (h @ p["wk"]).reshape(B, H, Dh)
                v1 = (h @ p["wv"]).reshape(B, H, Dh)
                # write THIS slot's row through the page table, then
                # attend over the view including it (mask is kpos > pos,
                # row pos itself attends — same as the contiguous step)
                kv = {"k": kv["k"].at[li, pg_w, off].set(k1),
                      "v": kv["v"].at[li, pg_w, off].set(v1)}
                ka, va = kv["k"][li], kv["v"][li]
                if use_pallas:
                    from znicz_tpu.ops.pallas.decode import \
                        paged_flash_decode
                    o = paged_flash_decode(q[:, 0], ka, va, pt, pos + 1,
                                           interpret=interp)
                    o = o.astype(va.dtype).reshape(B, 1, -1)
                else:
                    o = self._paged_attend(jnp, q, ka, va, pt, pos)
                x = x + o @ p["wo"]
                m = _layer_norm(x, p["ln2_g"], p["ln2_b"])
                x = x + (jax.nn.gelu(m @ p["w1"] + p["b1"]) @ p["w2"]
                         + p["b2"])
            logits = (x @ ps["head"]).astype(jnp.float32)
            return kv, logits[:, 0]

        return jax.jit(decode, donate_argnums=self._donate)

    def _build_pverify(self, key):
        import jax
        import jax.numpy as jnp

        from znicz_tpu.parallel.blocks import _layer_norm

        p_view, q_len = key
        H, Dh, page = self.heads, self.head_dim, self.page
        cdt = self._cast_policy()
        t_view = p_view * page

        def verify(params, kv, pt, pos, tokens):
            ps = jax.tree.map(lambda w: w.astype(cdt), params)
            B = tokens.shape[0]
            x = ps["emb"][tokens]                    # (B, Q, d)
            rows = pos[:, None] + jnp.arange(q_len)[None, :]  # (B, Q)
            pg_w = jnp.take_along_axis(pt, rows // page, axis=1)
            off = rows % page
            kpos = jnp.arange(t_view)
            li = 0
            for p in ps["blocks"]:
                h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
                q = (h @ p["wq"]).reshape(B, q_len, H, Dh)
                k1 = (h @ p["wk"]).reshape(B, q_len, H, Dh)
                v1 = (h @ p["wv"]).reshape(B, q_len, H, Dh)
                kv = {"k": kv["k"].at[li, pg_w, off].set(k1),
                      "v": kv["v"].at[li, pg_w, off].set(v1)}
                kc = kv["k"][li][pt].reshape(B, t_view, H, Dh)
                vc = kv["v"][li][pt].reshape(B, t_view, H, Dh)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                               preferred_element_type=jnp.float32)
                s = s / np.sqrt(Dh).astype(s.dtype)
                # per-query causal frontier: query i (row pos+i) sees
                # rows <= pos+i — draft rows beyond it stay invisible
                dead = kpos[None, None, :] > rows[:, :, None]
                s = jnp.where(dead[:, None, :, :],
                              jnp.asarray(-1e30, s.dtype), s)
                o = self._attend(jnp, s, vc).reshape(B, q_len, -1)
                x = x + o @ p["wo"]
                m = _layer_norm(x, p["ln2_g"], p["ln2_b"])
                x = x + (jax.nn.gelu(m @ p["w1"] + p["b1"]) @ p["w2"]
                         + p["b2"])
                li += 1
            logits = (x @ ps["head"]).astype(jnp.float32)
            return kv, logits                        # (B, Q, V)

        return jax.jit(verify, donate_argnums=self._donate)

    @property
    def _donate(self) -> tuple:
        """Donate the arena buffers so decode updates in place on
        accelerators; CPU XLA cannot honor the donation (it would warn
        per program), so the copy stays explicit there."""
        import jax

        return (1,) if jax.default_backend() != "cpu" else ()

    # -- public paged API ----------------------------------------------------
    def adopt_paged(self, kv1, pages) -> None:
        """Scatter a prefilled single-request contiguous cache
        ``kv1 (L, 1, T_p, H, Dh)`` into the arena at ``pages`` — the
        admission splice.  ``pages`` may be SHORTER than the prefill
        bucket spans (a 130-token prompt in a 256 bucket owns 9 pages,
        not 16): the scatter's tail chunks — masked bucket padding — are
        routed to the scratch page."""
        t_p = int(kv1["k"].shape[2])
        n = self.pages_for(t_p)
        if len(pages) > n:
            raise ValueError(f"{len(pages)} pages for a {t_p}-row "
                             f"prefill ({n} chunks)")
        fn = self._program(self._padopt, t_p, self._build_padopt,
                           "padopt")
        pg = np.zeros(n, np.int32)                   # tail -> scratch
        pg[:len(pages)] = np.asarray(pages, np.int32)
        self._arena = fn(self._arena, kv1, pg)

    def _check_view(self, page_table, pos, rows_ahead: int):
        pt = np.asarray(page_table, np.int32)
        pos = np.asarray(pos, np.int32)
        if pt.ndim != 2 or pt.shape[0] != self.batch:
            raise ValueError(f"page_table must be ({self.batch}, "
                             f"view); got {pt.shape}")
        p_view = pt.shape[1]
        if p_view not in self.page_buckets:
            raise ValueError(f"page-table view {p_view} is not a "
                             f"compiled bucket {self.page_buckets}")
        if pos.min() < 0 or int(pos.max()) + rows_ahead > p_view * \
                self.page:
            # same clamp hazard as the contiguous decode: an
            # out-of-view row would silently write a wrong page
            raise ValueError(
                f"rows [{int(pos.min())}, {int(pos.max()) + rows_ahead}"
                f") outside the {p_view * self.page}-row page view")
        return pt, pos, p_view

    def decode_paged(self, page_table, pos, token) -> np.ndarray:
        """One batched decode step through the page table; updates the
        shared arena in place (functionally: the arena buffer is
        rebound) and returns host logits ``(batch, vocab)``."""
        pt, pos, p_view = self._check_view(page_table, pos, 1)
        fn = self._program(self._pdecode, p_view, self._build_pdecode,
                           "pdecode")
        self._arena, logits = fn(self._params, self._arena, pt, pos,
                                 np.asarray(token, np.int32))
        with self._lock:
            self.decode_steps += 1
            self.tokens_decoded += int(pos.size)
        return np.asarray(logits)

    def verify_paged(self, page_table, pos, tokens) -> np.ndarray:
        """The speculative target pass: process ``tokens (batch, Q)``
        (last accepted token + Q-1 draft proposals) in one dispatch,
        writing Q rows per slot, and return logits ``(batch, Q, vocab)``
        — position ``i``'s row predicts the token after ``tokens[:i]``,
        which is exactly what the greedy acceptance rule compares."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2:
            raise ValueError(f"verify tokens must be (batch, q); got "
                             f"{tokens.shape}")
        q_len = tokens.shape[1]
        pt, pos, p_view = self._check_view(page_table, pos, q_len)
        fn = self._program(self._pverify, (p_view, q_len),
                           self._build_pverify, "pverify")
        self._arena, logits = fn(self._params, self._arena, pt, pos,
                                 tokens)
        with self._lock:
            self.decode_steps += 1
            self.tokens_decoded += int(tokens.size)
        return np.asarray(logits)

    def warmup(self, spec_k: int | None = None) -> int:
        """Materialize every compiled shape — prompt prefills, adopt
        scatters, decode per page-view bucket, and (when ``spec_k`` is
        given) the verify program per view — so live traffic compiles
        nothing.  All warmup writes land on the scratch page."""
        import time

        t0 = time.perf_counter()
        for b in self.buckets:
            kv1, _ = self.prefill([0], bucket=b)
            self.adopt_paged(kv1, [])                # all-scratch splice
        zeros = np.zeros(self.batch, np.int32)
        for pv in self.page_buckets:
            pt = np.zeros((self.batch, pv), np.int32)
            self.decode_paged(pt, zeros, zeros)
            # verify writes spec_k+1 rows, so live traffic can only
            # ever dispatch it at views that hold them (the batcher's
            # _ensure_pages guarantees pages*page >= pos+k+1) — a
            # narrower view would just crash warmup here
            if spec_k and pv * self.page >= spec_k + 1:
                self.verify_paged(pt, zeros,
                                  np.zeros((self.batch, spec_k + 1),
                                           np.int32))
        dt = time.perf_counter() - t0
        self.info(f"paged warmup: {len(self.buckets)} prefill buckets "
                  f"+ {len(self.page_buckets)} page views in {dt:.2f}s "
                  f"— {self.compile_count} programs compiled")
        return self.compile_count

    def stats(self) -> dict:
        out = super().stats()
        out.update({
            "paged": True, "page": self.page,
            "arena_pages": self.arena_pages,
            "pages_total": self.ledger.total,
            "pages_used": self.ledger.used,
            "pages_peak": self.ledger.peak_used,
            "arena_bytes": self.arena_bytes(),
            "use_pallas": self.use_pallas,
        })
        return out


def truncate_draft(params, n_layers: int):
    """Derive a layer-truncated draft from a target param pytree: same
    embedding, same head (same charmap vocab by construction), first
    ``n_layers`` blocks.  Early-exit drafting — the zero-extra-training
    way to get a cheaper proposer whose logits track the target's."""
    blocks = params["blocks"]
    n_layers = int(n_layers)
    if not 1 <= n_layers < len(blocks):
        raise ValueError(f"draft needs 1 <= n_layers < {len(blocks)}, "
                         f"got {n_layers}")
    return {"emb": np.asarray(params["emb"], np.float32),
            "head": np.asarray(params["head"], np.float32),
            "blocks": [{k: np.asarray(a, np.float32)
                        for k, a in blk.items()}
                       for blk in blocks[:n_layers]]}
