"""KV-cache incremental decode — the device core of the generative
serving plane (ISSUE 10).

Autoregressive serving recomputes nothing: each request's attention
keys/values live in a preallocated device cache, ``prefill`` runs the
prompt once (filling the cache and yielding the first next-token
logits), and every subsequent token is one ``decode`` step that writes
a single cache row and attends over the rows written so far.  Cache
buffers are padded to power-of-two *cache-length buckets*
(``engine.bucket_sizes`` — the serve plane's one compile-shape policy),
so each program compiles once per bucket and steady-state decoding
triggers **zero** recompiles across mixed request lengths within a
bucket; ``compile_count`` makes that assertable exactly like
``BatchEngine``.

The decode math deliberately mirrors the training stack's layers
(``parallel/blocks.py``) op by op — the same ``_layer_norm``, the
same ``masked_scores`` scale/mask constants, the same f32 softmax
accumulators ``ring_attention`` uses at ring size 1, the same compute-
dtype cast policy — and the whole path is pinned against the full-pass
``make_logits_fn`` oracle the train step's module builds: greedy
decode through the cache must reproduce N full forward passes token for
token (tests/test_generate.py).  Dense FFN blocks only; MoE decode is
refused loudly (expert routing under a one-token batch is a different
serving problem).

Sampling stays on the host: :class:`TokenSampler` is seeded
temperature / top-k sampling over the returned logits, so a fixed
``(seed, temperature, top_k)`` triple reproduces a generation exactly
and the compiled programs stay sampling-free (no per-request PRNG state
threading through jit).
"""

from __future__ import annotations

import threading

import numpy as np

from znicz_tpu.core.logger import Logger
from znicz_tpu.serve.engine import bucket_sizes


class TokenSampler:
    """Seeded, deterministic next-token sampling over host logits.

    ``temperature == 0`` (or ``top_k == 1``) is greedy argmax — ties
    break toward the lowest id, matching ``np.argmax`` on both the
    cache path and the full-forward oracle.  Otherwise logits are
    temperature-scaled, optionally truncated to the ``top_k`` largest,
    and sampled from the renormalized softmax with this sampler's own
    ``numpy`` Generator — one sampler per request, so concurrent
    generations never share PRNG state.
    """

    def __init__(self, seed: int = 0, temperature: float = 1.0,
                 top_k: int = 0) -> None:
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        self.seed = int(seed)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.rng = np.random.default_rng(self.seed)

    def sample(self, logits: np.ndarray) -> int:
        z = np.asarray(logits, np.float64).ravel()
        if self.temperature == 0.0 or self.top_k == 1:
            return int(np.argmax(z))
        z = z / self.temperature
        if self.top_k and self.top_k < z.size:
            # keep the top_k largest; the cutoff uses partition so ties
            # at the boundary keep every value >= the k-th largest
            cut = np.partition(z, -self.top_k)[-self.top_k]
            z = np.where(z >= cut, z, -np.inf)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self.rng.choice(z.size, p=p))


class KVDecoder(Logger):
    """Bucketed incremental decoder over a transformer param pytree.

    ``params``: the ``parallel/params.py`` pytree (``emb``,
    ``head``, ``blocks``) as numpy or jax arrays; placed on device
    once.  ``heads`` cannot be derived from the arrays and must be
    given; everything else (layers, d, ff, vocab) is read off the
    shapes.  ``max_len`` bounds prompt+generation length and defines
    the bucket set; ``batch`` is the fixed slot width compiled into the
    batched ``decode`` program (1 for single-request use, >1 for the
    continuous batcher).

    Compiled programs, one per cache-length bucket:

    - ``prefill(params, tokens(1,T), length) -> (kv, logits(V,))`` —
      full prompt pass, cache for all T rows, logits at ``length-1``;
    - ``decode(params, kv, pos(B,), token(B,)) -> (kv, logits(B,V))``
      — write row ``pos`` per slot, attend over rows ``<= pos``;
    - ``adopt(kv_batch, kv1, slot) -> kv_batch`` — splice a prefilled
      single-request cache into a batch slot (continuous admission).

    ``warmup()`` materializes every bucket's programs so steady state
    compiles nothing; ``compile_count`` counts first-executions exactly
    like ``BatchEngine.compile_count``.
    """

    def __init__(self, params, heads: int, max_len: int = 256,
                 batch: int = 1) -> None:
        super().__init__()
        import jax

        from znicz_tpu.parallel.arch import mechanisms_of_params

        extra = mechanisms_of_params(params)
        if extra:
            raise NotImplementedError(
                f"KV-cache decode serves the GPT-shaped block only; this "
                f"model has {', '.join(extra)} (no convolution state "
                f"beside keys and values, no experts when decoding)")
        if any("ew1" in blk for blk in params["blocks"]):
            raise NotImplementedError(
                "KV-cache decode supports dense FFN blocks only; MoE "
                "decode (expert routing at batch-of-one) is not wired")
        self.n_layers = len(params["blocks"])
        self.vocab, self.d = (int(s) for s in np.shape(params["emb"]))
        self.ff = int(np.shape(params["blocks"][0]["w1"])[1])
        self.heads = int(heads)
        if self.d % self.heads:
            raise ValueError(f"heads={heads} must divide d={self.d}")
        self.head_dim = self.d // self.heads
        self.max_len = int(max_len)
        self.batch = int(batch)
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        self.buckets = bucket_sizes(self.max_len)
        self._params = jax.device_put(jax.tree.map(
            lambda a: np.asarray(a, np.float32), params))
        self._prefill: dict = {}     # bucket -> jitted
        self._decode: dict = {}
        self._adopt: dict = {}
        self._seen: set = set()      # (kind, bucket) first-executions
        self.compile_count = 0
        self.prefill_count = 0
        self.decode_steps = 0        # batched decode dispatches
        self.tokens_decoded = 0      # slot-tokens produced by decode
        self._lock = threading.Lock()
        from znicz_tpu import compilecache
        compilecache.ensure()

    # -- shape policy --------------------------------------------------------
    def bucket_for(self, total_len: int) -> int:
        """Smallest cache bucket covering ``total_len`` tokens."""
        if total_len < 1:
            raise ValueError("empty sequence")
        if total_len > self.max_len:
            # admission-time rejection (400, never a burned slot): the
            # message names the configured limit so a client knows what
            # to shrink — prompt + max_tokens must fit --max-len
            raise ValueError(
                f"sequence of {total_len} tokens (prompt + max_tokens) "
                f"exceeds this server's max_len {self.max_len} "
                f"(--max-len)")
        for b in self.buckets:
            if total_len <= b:
                return b
        return self.max_len

    def _count(self, kind: str, bucket: int) -> None:
        with self._lock:
            if (kind, bucket) not in self._seen:
                self._seen.add((kind, bucket))
                self.compile_count += 1
                self.debug(f"compiling {kind} for cache bucket {bucket} "
                           f"({self.compile_count} programs)")

    # -- compiled program builders ------------------------------------------
    def _cast_policy(self):
        from znicz_tpu.parallel.arch import _default_compute_dtype
        return _default_compute_dtype(None)

    def _attend(self, jnp, s, v_cache):
        """Softmax attention from f32 scores ``s (B,H,Q,T)`` and cached
        values ``(B,T,H,Dh)`` — the exact online-softmax recipe
        ``ring_attention`` applies at ring size 1 (f32 max/exp/sum
        accumulators, values matmul at the value dtype with an f32
        accumulator), so the cache path and the training forward agree
        to the last rounding."""
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_cache.dtype),
                       v_cache, preferred_element_type=jnp.float32)
        o = (o / l[..., None]).astype(v_cache.dtype)
        return jnp.transpose(o, (0, 2, 1, 3))        # (B, Q, H, Dh)

    def _build_prefill(self, bucket: int):
        import jax
        import jax.numpy as jnp

        from znicz_tpu.ops.attention import masked_scores
        from znicz_tpu.parallel.blocks import _layer_norm

        H, Dh = self.heads, self.head_dim
        cdt = self._cast_policy()

        def prefill(params, tokens, length):
            # tokens (1, bucket) int32, padded past `length`; the padded
            # rows compute garbage K/V that decode overwrites before any
            # mask exposes them (row pos is written before it is read)
            ps = jax.tree.map(lambda w: w.astype(cdt), params)
            x = ps["emb"][tokens]                    # (1, T, d)
            b, t = x.shape[:2]
            kpos = jnp.arange(t)
            ks, vs = [], []
            for p in ps["blocks"]:
                h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
                q = (h @ p["wq"]).reshape(b, t, H, Dh)
                k = (h @ p["wk"]).reshape(b, t, H, Dh)
                v = (h @ p["wv"]).reshape(b, t, H, Dh)
                ks.append(k)
                vs.append(v)
                s = masked_scores(jnp, q, k, True)   # causal, f32
                s = jnp.where((kpos >= length)[None, None, None, :],
                              jnp.asarray(-1e30, s.dtype), s)
                o = self._attend(jnp, s, v).reshape(b, t, -1)
                x = x + o @ p["wo"]
                m = _layer_norm(x, p["ln2_g"], p["ln2_b"])
                x = x + (jax.nn.gelu(m @ p["w1"] + p["b1"]) @ p["w2"]
                         + p["b2"])
            logits = (x @ ps["head"]).astype(jnp.float32)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0, keepdims=False)
            return {"k": jnp.stack(ks), "v": jnp.stack(vs)}, last

        return jax.jit(prefill)

    def _build_decode(self, bucket: int):
        import jax
        import jax.numpy as jnp

        from znicz_tpu.parallel.blocks import _layer_norm

        H, Dh = self.heads, self.head_dim
        cdt = self._cast_policy()
        write = jax.vmap(
            lambda cache, new, p: jax.lax.dynamic_update_slice(
                cache, new, (p, 0, 0)))              # over the slot dim

        def decode(params, kv, pos, token):
            # kv {"k"/"v": (L, B, T, H, Dh)}; pos (B,) row to write (==
            # current length); token (B,) the token to process
            ps = jax.tree.map(lambda w: w.astype(cdt), params)
            B = token.shape[0]
            x = ps["emb"][token][:, None, :]         # (B, 1, d)
            kpos = jnp.arange(bucket)
            ks, vs = [], []
            for li, p in enumerate(ps["blocks"]):
                h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
                q = (h @ p["wq"]).reshape(B, 1, H, Dh)
                k1 = (h @ p["wk"]).reshape(B, 1, H, Dh)
                v1 = (h @ p["wv"]).reshape(B, 1, H, Dh)
                kc = write(kv["k"][li], k1, pos)
                vc = write(kv["v"][li], v1, pos)
                ks.append(kc)
                vs.append(vc)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                               preferred_element_type=jnp.float32)
                s = s / np.sqrt(Dh).astype(s.dtype)
                # keys past this slot's current position are unwritten
                # (or stale rows of a previous occupant): same -1e30
                # mask constant as masked_scores
                dead = kpos[None, :] > pos[:, None]  # (B, T)
                s = jnp.where(dead[:, None, None, :],
                              jnp.asarray(-1e30, s.dtype), s)
                o = self._attend(jnp, s, vc).reshape(B, 1, -1)
                x = x + o @ p["wo"]
                m = _layer_norm(x, p["ln2_g"], p["ln2_b"])
                x = x + (jax.nn.gelu(m @ p["w1"] + p["b1"]) @ p["w2"]
                         + p["b2"])
            logits = (x @ ps["head"]).astype(jnp.float32)
            return {"k": jnp.stack(ks), "v": jnp.stack(vs)}, logits[:, 0]

        return jax.jit(decode)

    def _build_adopt(self, bucket: int):
        import jax

        def adopt(kv, kv1, slot):
            return jax.tree.map(
                lambda c, c1: jax.lax.dynamic_update_slice(
                    c, c1, (0, slot) + (0,) * (c.ndim - 2)), kv, kv1)

        return jax.jit(adopt)

    def _program(self, cache: dict, bucket: int, builder, kind: str):
        if bucket not in cache:
            cache[bucket] = builder(bucket)
        self._count(kind, bucket)
        return cache[bucket]

    # -- public API ----------------------------------------------------------
    def alloc(self, bucket: int):
        """Zeroed batch cache for ``bucket`` — ``{"k"/"v"}`` of shape
        ``(layers, batch, bucket, heads, head_dim)`` on device."""
        import jax.numpy as jnp

        shape = (self.n_layers, self.batch, bucket, self.heads,
                 self.head_dim)
        dt = self._cast_policy()
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def grow(self, kv, new_bucket: int):
        """Pad a batch cache out to a larger bucket (zeros past the old
        length — every live row index is below it, and per-slot ``pos``
        masks keep the padding invisible).  Bucket transitions are the
        only place cache shapes change; within a bucket nothing ever
        recompiles."""
        import jax.numpy as jnp

        old = kv["k"].shape[2]
        if new_bucket < old:
            raise ValueError(f"grow to {new_bucket} < current {old}")
        if new_bucket == old:
            return kv
        pad = [(0, 0)] * 5
        pad[2] = (0, new_bucket - old)
        return {name: jnp.pad(c, pad) for name, c in kv.items()}

    def prefill(self, tokens, bucket: int | None = None):
        """Run the prompt through the full pass: ``tokens`` (1-D int
        sequence) -> ``(kv1, logits)`` — a single-request cache
        ``(L, 1, bucket, H, Dh)`` plus the next-token logits as a host
        f32 vector.  With ``batch == 1`` the returned cache feeds
        :meth:`decode` directly; the continuous batcher splices it into
        a slot via :meth:`adopt`."""
        ids = np.asarray(tokens, np.int32).ravel()
        if ids.size < 1:
            raise ValueError("empty prompt")
        if ids.min() < 0 or ids.max() >= self.vocab:
            raise ValueError(f"token ids must be in [0, {self.vocab}); "
                             f"got range [{ids.min()}, {ids.max()}]")
        bucket = self.bucket_for(ids.size) if bucket is None else bucket
        if ids.size > bucket:
            raise ValueError(f"prompt of {ids.size} tokens > bucket "
                             f"{bucket}")
        fn = self._program(self._prefill, bucket, self._build_prefill,
                           "prefill")
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :ids.size] = ids
        kv1, logits = fn(self._params, padded, np.int32(ids.size))
        with self._lock:
            self.prefill_count += 1
        return kv1, np.asarray(logits)

    def decode(self, kv, pos, token):
        """One batched decode step: ``pos``/``token`` arrays of width
        ``batch`` -> ``(kv, logits (batch, vocab))`` with logits on
        host.  Slots whose row is not meant to advance simply get their
        next cache row overwritten again later — the caller (continuous
        batcher) owns slot liveness."""
        bucket = int(kv["k"].shape[2])
        pos = np.asarray(pos, np.int32)
        if pos.max() >= bucket or pos.min() < 0:
            # dynamic_update_slice CLAMPS out-of-range starts — a write
            # past the cache (or a negative position landing on row 0)
            # would silently corrupt a live row instead of failing; the
            # batcher grows the bucket before this
            raise ValueError(f"decode positions [{int(pos.min())}, "
                             f"{int(pos.max())}] outside cache bucket "
                             f"{bucket}; grow() first")
        fn = self._program(self._decode, bucket, self._build_decode,
                           "decode")
        kv, logits = fn(self._params, kv, pos,
                        np.asarray(token, np.int32))
        with self._lock:
            self.decode_steps += 1
            self.tokens_decoded += int(np.asarray(pos).size)
        return kv, np.asarray(logits)

    def adopt(self, kv, kv1, slot: int):
        """Splice a prefilled single-request cache into batch ``slot``."""
        bucket = int(kv["k"].shape[2])
        if int(kv1["k"].shape[2]) != bucket:
            kv1 = self.grow(kv1, bucket)
        fn = self._program(self._adopt, bucket, self._build_adopt,
                           "adopt")
        return fn(kv, kv1, np.int32(slot))

    def warmup(self) -> int:
        """Materialize every bucket's programs (prefill + decode, and
        adopt when batched) so live traffic compiles nothing; returns
        ``compile_count``."""
        import time

        t0 = time.perf_counter()
        for b in self.buckets:
            kv1, _ = self.prefill([0], bucket=b)
            if self.batch == 1:
                kv = kv1
            else:
                kv = self.adopt(self.alloc(b), kv1, 0)
            # row 0 is always in range (bucket 1 has nothing else);
            # warmup only needs the program materialized, not a real
            # generation
            self.decode(kv, np.zeros(self.batch, np.int32),
                        np.zeros(self.batch, np.int32))
        dt = time.perf_counter() - t0
        self.info(f"warmup: {len(self.buckets)} cache buckets in "
                  f"{dt:.2f}s — {self.compile_count} programs compiled")
        return self.compile_count

    # -- single-request convenience -----------------------------------------
    def generate(self, prompt, max_new_tokens: int,
                 sampler: TokenSampler | None = None,
                 on_token=None) -> list:
        """Prefill + decode loop for a lone request (``batch == 1``):
        returns the generated ids; ``on_token(id)`` streams them as
        produced.  The CLI one-shot mode and the bit-equivalence pin
        run through exactly this path."""
        if self.batch != 1:
            raise ValueError("generate() needs a batch=1 decoder; the "
                             "continuous batcher owns batched decode")
        # default is GREEDY (temperature 0), matching the CLI default —
        # an unconfigured generate() must be reproducible
        sampler = sampler if sampler is not None else \
            TokenSampler(temperature=0.0)
        ids = np.asarray(prompt, np.int32).ravel()
        bucket = self.bucket_for(ids.size + max_new_tokens)
        kv, logits = self.prefill(ids, bucket=bucket)
        out = []
        pos = ids.size
        for _ in range(max_new_tokens):
            tok = sampler.sample(logits)
            out.append(tok)
            if on_token is not None:
                on_token(tok)
            if len(out) == max_new_tokens:
                break
            kv, batch_logits = self.decode(
                kv, np.asarray([pos], np.int32),
                np.asarray([tok], np.int32))
            logits = batch_logits[0]
            pos += 1
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "n_layers": self.n_layers, "d": self.d,
                "heads": self.heads, "ff": self.ff, "vocab": self.vocab,
                "max_len": self.max_len, "batch": self.batch,
                "buckets": list(self.buckets),
                "compile_count": self.compile_count,
                "prefill_count": self.prefill_count,
                "decode_steps": self.decode_steps,
                "tokens_decoded": self.tokens_decoded,
            }
