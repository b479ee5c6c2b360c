"""Forward export — rebuild of veles.znicz nn_units.py :: ForwardExporter
and the libVeles/libZnicz inference path (SURVEY.md §4.5).

The reference packaged the forward chain + weights for the C++ inference
runtime; the TPU equivalent is an explicit package: architecture JSON
(the StandardWorkflow layer specs) + weights npz in one file, reloadable
into a jitted forward function with no trace of the training workflow.

ISSUE 7 (compile-latency plane) adds ahead-of-time serving artifacts —
TensorFlow's deploy-compiled-programs-once model (Abadi et al. 2016)
instead of trace-on-first-request: :func:`attach_aot` compiles one
``jax.jit(forward).lower(...).compile()`` executable per serve-engine
bucket shape and stores the serialized executables INSIDE the package
(``__aot__<bucket>`` entries), so ``python -m znicz_tpu serve`` boots
with ``compile_count == 0``.  AOT executables are device-pinned: the
package carries a backend fingerprint (jax version, platform, device
kind, device count) that the loader CHECKS, never trusts — any mismatch
falls back to JIT with a logged reason (docs/COMPILE.md).
"""

from __future__ import annotations

import json
import os

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from znicz_tpu.units.nn_units import MatchingObject

#: schema tag for the AOT block inside a forward package's meta
AOT_FORMAT = "znicz_tpu.aot/1"

#: npz entry prefix for serialized per-bucket executables
_AOT_PREFIX = "__aot__"


def export_forward(workflow, path: str, use_ema: bool = False,
                   aot_max_batch: int | None = None) -> str:
    """Package a StandardWorkflow's forward chain (layer specs + trained
    weights) into ``path`` (.npz).  ``use_ema=True`` ships the fused
    step's Polyak-averaged mirrors instead of the raw weights (the usual
    serving choice when ``ema_decay`` was on).  ``aot_max_batch`` also
    precompiles + embeds serving executables for every engine bucket up
    to that batch size (:func:`attach_aot`) — the exporting host's
    backend is the fingerprint, so export on the device class that will
    serve."""
    if not hasattr(workflow, "layer_specs"):
        raise TypeError("export_forward needs a StandardWorkflow (layer "
                        "specs carry the architecture)")
    step = getattr(workflow, "step", None)
    if step is not None and getattr(step, "_params", None) is not None:
        step.sync_to_units()
    ema = None
    if use_ema:
        if step is None or getattr(step, "ema_decay", None) is None:
            raise ValueError("use_ema=True needs a fused workflow built "
                             "with ema_decay")
        if getattr(step, "_params", None) is None:
            raise ValueError("use_ema=True needs an initialized workflow "
                             "(the EMA mirrors live in the step's device "
                             "params)")
        ema = step.ema_params()
    arch = []
    arrays = {}
    for i, ((type_name, _unit_name, fwd_kwargs, _gd), fwd) in enumerate(
            zip(workflow.layer_specs, workflow.forwards)):
        arch.append({"type": type_name, "config": fwd_kwargs})
        for attr, ema_key in (("weights", "w"), ("bias", "b")):
            arr = getattr(fwd, attr)
            if arr:
                if ema is not None and ema_key in ema[i]:
                    arrays[f"{i}.{attr}"] = np.asarray(ema[i][ema_key])
                else:
                    arrays[f"{i}.{attr}"] = np.asarray(arr.map_read())
    meta = {"format": "znicz_tpu.forward", "version": 1, "arch": arch,
            "name": workflow.name, "ema": bool(use_ema),
            "input_shape": list(workflow.loader.minibatch_data.shape[1:])}
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, __arch__=np.array(json.dumps(meta)), **arrays)
    os.replace(tmp, path)
    if aot_max_batch is not None:
        attach_aot(path, max_batch=aot_max_batch)
    return path


# -- generative LM packages (ISSUE 10) ---------------------------------------

#: schema tag for transformer LM packages (serve/kvcache.py consumers)
LM_FORMAT = "znicz_tpu.lm/1"


def _lm_arch(params, heads: int, prefix: str = ""):
    """-> (arch meta dict, flat arrays dict) for one transformer param
    pytree — shared by the target and draft halves of a package."""
    vocab, d = (int(s) for s in np.shape(params["emb"]))
    blocks = params["blocks"]
    from znicz_tpu.parallel.arch import mechanisms_of_params

    extra = mechanisms_of_params(params)
    if extra:
        raise ValueError(
            f"export_lm packages the GPT-shaped block only; this model "
            f"has {', '.join(extra)}, which serve/ does not decode")
    if any("ew1" in blk for blk in blocks):
        raise ValueError("export_lm supports dense FFN stacks only "
                         "(KV-cache decode does not serve MoE)")
    ff = int(np.shape(blocks[0]["w1"])[1])
    if d % int(heads):
        raise ValueError(f"heads={heads} must divide d={d}")
    arrays = {f"{prefix}emb": np.asarray(params["emb"], np.float32),
              f"{prefix}head": np.asarray(params["head"], np.float32)}
    for i, blk in enumerate(blocks):
        for key, arr in blk.items():
            arrays[f"{prefix}blocks.{i}.{key}"] = \
                np.asarray(arr, np.float32)
    meta = {"n_layers": len(blocks), "d": d, "heads": int(heads),
            "ff": ff, "vocab": vocab}
    return meta, arrays


def export_lm(params, path: str, *, heads: int, charmap=None,
              name: str = "lm", draft_params=None,
              draft_heads: int | None = None) -> str:
    """Package a ``parallel/params.py`` param pytree as a
    generative serving artifact (.npz): flat weight arrays plus an
    ``__lm__`` meta block carrying the architecture (layers/d/heads/ff/
    vocab — everything :class:`~znicz_tpu.serve.kvcache.KVDecoder`
    needs) and, for char LMs, the ``charmap`` (id -> character) so the
    server can speak text on the wire.  ``heads`` is the one
    architecture fact the shapes cannot reveal.

    ``draft_params`` ships a smaller DRAFT transformer over the same
    vocab alongside the target (ISSUE 12): its arrays ride under a
    ``draft.`` prefix and its architecture under ``meta["draft"]``, so
    ``--speculative`` serving boots both from one artifact
    (:func:`load_lm_draft`).  ``draft_heads`` defaults to ``heads``."""
    arch, arrays = _lm_arch(params, heads)
    vocab = arch["vocab"]
    if charmap is not None and len(charmap) != vocab:
        raise ValueError(f"charmap has {len(charmap)} entries but the "
                         f"embedding carries vocab {vocab}")
    meta = {"format": LM_FORMAT, "name": name, **arch,
            "charmap": list(charmap) if charmap is not None else None,
            "draft": None}
    if draft_params is not None:
        draft_arch, draft_arrays = _lm_arch(
            draft_params, heads if draft_heads is None else draft_heads,
            prefix="draft.")
        if draft_arch["vocab"] != vocab:
            raise ValueError(
                f"draft vocab {draft_arch['vocab']} != target vocab "
                f"{vocab} — the draft must share the charmap")
        meta["draft"] = draft_arch
        arrays.update(draft_arrays)
    # pid-unique temp (the PR 9 snapshot lesson): two processes
    # exporting to the same path must not tear a shared .tmp
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, __lm__=np.array(json.dumps(meta)),
                            **arrays)
    os.replace(tmp, path)
    return path


def load_lm(path: str):
    """-> ``(params, meta)`` from an :func:`export_lm` package.  The
    params come back as the numpy pytree ``KVDecoder`` (and
    ``make_logits_fn``) consume; raises ``ValueError`` on a package
    that is not an LM artifact (e.g. a ``forward`` package handed to
    the wrong CLI)."""
    with np.load(path, allow_pickle=False) as z:
        if "__lm__" not in z:
            raise ValueError(f"{path!r} is not an LM package (no __lm__ "
                             "meta; `znicz_tpu serve` handles forward "
                             "packages)")
        meta = json.loads(str(z["__lm__"]))
        if meta.get("format") != LM_FORMAT:
            raise ValueError(f"unsupported LM package format "
                             f"{meta.get('format')!r} (want {LM_FORMAT})")
        blocks: list = [{} for _ in range(int(meta["n_layers"]))]
        for key in z.files:
            if key.startswith("blocks."):
                _, idx, leaf = key.split(".", 2)
                if not 0 <= int(idx) < len(blocks):
                    # ValueError, not IndexError: the CLI's cannot-load
                    # rc=2 path catches the former
                    raise ValueError(
                        f"{path!r} carries {key!r} but meta declares "
                        f"only {len(blocks)} layer(s)")
                blocks[int(idx)][leaf] = z[key]
        params = {"emb": z["emb"], "head": z["head"], "blocks": blocks}
    if any(not blk for blk in blocks):
        raise ValueError(f"{path!r} is missing block arrays for "
                         f"{sum(not b for b in blocks)} of "
                         f"{len(blocks)} layers")
    return params, meta


def load_lm_draft(path: str):
    """-> ``(draft_params, draft_meta)`` from a package exported with
    ``draft_params``, or ``(None, None)`` when the package carries no
    draft.  The draft pytree has the same shape contract as the target
    (``emb`` / ``head`` / ``blocks``) and boots a
    :class:`~znicz_tpu.serve.paged.PagedKVDecoder` directly."""
    with np.load(path, allow_pickle=False) as z:
        if "__lm__" not in z:
            raise ValueError(f"{path!r} is not an LM package")
        meta = json.loads(str(z["__lm__"]))
        draft_meta = meta.get("draft")
        if not draft_meta:
            return None, None
        blocks: list = [{} for _ in range(int(draft_meta["n_layers"]))]
        for key in z.files:
            if key.startswith("draft.blocks."):
                _, _, idx, leaf = key.split(".", 3)
                if not 0 <= int(idx) < len(blocks):
                    raise ValueError(
                        f"{path!r} carries {key!r} but the draft meta "
                        f"declares only {len(blocks)} layer(s)")
                blocks[int(idx)][leaf] = z[key]
        params = {"emb": z["draft.emb"], "head": z["draft.head"],
                  "blocks": blocks}
    if any(not blk for blk in blocks):
        raise ValueError(f"{path!r} draft is missing block arrays")
    return params, draft_meta


# -- ahead-of-time serving artifacts (ISSUE 7) -------------------------------

def aot_fingerprint() -> dict:
    """The backend identity an AOT executable is pinned to.  Serialized
    XLA executables embed device-specific code AND jax/xla version-
    specific calling conventions — every field must match at load time
    or the executable is untrusted (fall back to JIT, never crash).

    An AOT package serves from ONE device, the process's first, whatever
    ``num_devices`` reads: each bucket is compiled for it and loaded for
    it (:func:`_aot_load`), so on a four-chip host the other three run
    none of it."""
    import jaxlib.version

    dev = jax.devices()[0]
    return {"format": AOT_FORMAT, "jax": jax.__version__,
            "jaxlib": jaxlib.version.__version__,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "num_devices": jax.device_count()}


def _aot_load(payload, in_tree, out_tree):
    """Load one bucket's executable for the device it was compiled for:
    the process's first, where ``jnp.asarray`` puts the package's params.
    Left to itself ``deserialize_and_load`` loads for every device of
    the backend, and the first request then asks for that many shards."""
    from jax.experimental import serialize_executable as _se

    return _se.deserialize_and_load(payload, in_tree, out_tree,
                                    execution_devices=[jax.devices()[0]])


def aot_mismatch_reason(fp: dict) -> str | None:
    """Why a package's AOT fingerprint does not cover THIS process —
    None when it does.  The check is exact-match on every field: an
    executable compiled by any other jax/xla/device combination may
    load and then crash (or silently miscompute) mid-request."""
    try:
        current = aot_fingerprint()
    except Exception as exc:  # noqa: BLE001 — no backend at all
        return f"no jax backend available ({exc!r})"
    for key, want in current.items():
        have = fp.get(key)
        if have != want:
            return (f"{key} mismatch: package has {have!r}, this "
                    f"process has {want!r}")
    return None


def _aot_treedefs(params, x_leaf):
    """The (in_tree, out_tree) treedefs ``serialize_executable`` pairs
    with a payload, reconstructed from the loaded params instead of
    stored: the forward signature is fixed at ``(params, x) -> y``."""
    return (jtu.tree_structure(((params, x_leaf), {})),
            jtu.tree_structure(x_leaf))


def attach_aot(path: str, max_batch: int = 64,
               out: str | None = None) -> dict:
    """Precompile the package's forward for every serve-engine bucket
    shape on THIS host's backend and embed the serialized executables
    (``python -m znicz_tpu aot <pkg.npz>`` is the CLI face).  Returns
    the AOT meta block; ``out`` writes a copy instead of augmenting in
    place.

    Serialization demands a FRESH compile: an executable that came out
    of any compile cache — jax's persistent on-disk cache OR the
    in-process executable cache a prior compile-and-run of the same
    module populated — serializes WITHOUT its object code (the payload
    halves and later deserializes to XLA "Symbols not found"; both
    modes found the hard way).  So the persistent cache is bypassed,
    the forward is compiled under a process-unique module name no cache
    can already hold, and every payload is round-trip-verified
    deserializable before the package is written."""
    import uuid

    from jax.experimental import serialize_executable as _se

    from znicz_tpu.serve.engine import bucket_sizes

    fwd = ExportedForward(path, aot=False)
    buckets = bucket_sizes(int(max_batch))
    payloads, want_in, want_out = {}, None, None

    def aot_forward(params, x):
        return fwd._forward(params, x)

    # the module name jit derives from __name__ is part of every cache
    # key — a never-seen name guarantees never-cached compiles
    aot_forward.__name__ = f"aot_forward_{uuid.uuid4().hex[:10]}"
    from znicz_tpu import compilecache as _cc

    # compilecache.suspended() flips the process-global cache config off
    # (and back) under the module lock, with the jax latched-state reset
    # that makes the flip real in both directions — a concurrent
    # configure() cannot re-enable the cache mid-block
    with _cc.suspended():
        for b in buckets:
            xspec = jax.ShapeDtypeStruct((b,) + fwd.input_shape,
                                         jnp.float32)
            compiled = jax.jit(aot_forward).lower(fwd._params,
                                                  xspec).compile()
            payload, in_tree, out_tree = _se.serialize(compiled)
            if want_in is None:
                want_in, want_out = _aot_treedefs(fwd._params, xspec)
            if in_tree != want_in or out_tree != want_out:
                # the load path reconstructs treedefs instead of storing
                # them — a drift would deserialize into garbage calls
                raise RuntimeError(
                    "AOT treedef drift: serialize() returned a call "
                    "signature the loader would not reconstruct; "
                    "refusing to write an unloadable package")
            # round-trip check BEFORE writing: a payload that cannot
            # load here will never load anywhere
            _aot_load(payload, want_in, want_out)
            payloads[b] = np.frombuffer(payload, dtype=np.uint8)
    aot_meta = {"fingerprint": aot_fingerprint(),
                "buckets": list(buckets), "max_batch": int(max_batch),
                "dtype": "float32"}
    with np.load(path, allow_pickle=False) as zf:
        meta = json.loads(str(zf["__arch__"]))
        if meta.get("format") != "znicz_tpu.forward":
            raise ValueError(f"{path!r} is not a forward package")
        arrays = {k: zf[k] for k in zf.files
                  if k != "__arch__" and not k.startswith(_AOT_PREFIX)}
    meta["aot"] = aot_meta
    dest = out or path
    tmp = dest + ".tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(
            f, __arch__=np.array(json.dumps(meta)),
            **{f"{_AOT_PREFIX}{b}": p for b, p in payloads.items()},
            **arrays)
    os.replace(tmp, dest)
    return aot_meta


class ExportedForward:
    """A loaded forward package: jitted inference with no workflow
    machinery (the libZnicz-equivalent runtime).

    As a serve/engine.py backend it declares ``static_shapes = True``:
    jit compiles per input shape, so the engine pads requests to its
    bucketed batch shapes and steady-state serving never recompiles.

    When the package carries AOT executables (:func:`attach_aot`) and
    their fingerprint matches this process's backend, bucket-shaped
    batches run the deserialized compiled programs directly — zero JIT,
    zero compiles; ``precompiled_buckets`` tells the engine which
    shapes those are.  A fingerprint or deserialization failure logs
    ``aot_fallback_reason`` and serves through JIT exactly as before —
    outputs are the same compiled HLO either way, so results are
    bit-identical (pinned in tests/test_compilecache.py).
    """

    #: jit-per-shape — the serving engine must pad to fixed buckets
    static_shapes = True

    def __init__(self, path: str, aot: bool = True) -> None:
        # serve boot is a primary compile site: make sure the persistent
        # compilation cache is live before the first jit below
        from znicz_tpu import compilecache
        compilecache.ensure()
        with np.load(path, allow_pickle=False) as zf:
            meta = json.loads(str(zf["__arch__"]))
            if meta.get("format") != "znicz_tpu.forward":
                raise ValueError(f"{path!r} is not a forward package")
            self.meta = meta
            self.arrays = {k: zf[k] for k in zf.files
                           if k != "__arch__" and
                           not k.startswith(_AOT_PREFIX)}
            aot_payloads = {int(k[len(_AOT_PREFIX):]): zf[k].tobytes()
                            for k in zf.files
                            if k.startswith(_AOT_PREFIX)} if aot else {}
        self.name = meta["name"]
        self.input_shape = tuple(meta["input_shape"])
        self._units = []
        # rebuild bare forward units (no workflow) for their xla_apply
        for i, spec in enumerate(meta["arch"]):
            cls = MatchingObject.forwards[spec["type"]]
            unit = cls(None, **spec["config"])
            self._units.append(unit)
        self._params = []
        for i in range(len(self._units)):
            leaf = {}
            if f"{i}.weights" in self.arrays:
                leaf["w"] = jnp.asarray(self.arrays[f"{i}.weights"])
            if f"{i}.bias" in self.arrays:
                leaf["b"] = jnp.asarray(self.arrays[f"{i}.bias"])
            self._params.append(leaf)
        self._fn = jax.jit(self._forward)
        #: bucket batch size -> deserialized compiled executable
        self.precompiled_buckets: dict = {}
        #: why the package's AOT block was ignored (None = loaded or
        #: the package has none)
        self.aot_fallback_reason = None
        if aot_payloads:
            self._load_aot(meta.get("aot") or {}, aot_payloads)

    def _load_aot(self, aot_meta: dict, payloads: dict) -> None:
        """Deserialize the package's per-bucket executables — fingerprint
        CHECKED first (device-pinned artifacts are never trusted), any
        failure degrades to the JIT path with one logged reason."""
        import logging

        log = logging.getLogger("znicz_tpu.export")
        reason = aot_mismatch_reason(aot_meta.get("fingerprint") or {})
        if reason is None:
            try:
                in_tree, out_tree = _aot_treedefs(self._params, 0)
                self.precompiled_buckets = {
                    b: _aot_load(p, in_tree, out_tree)
                    for b, p in sorted(payloads.items())}
            except Exception as exc:  # noqa: BLE001 — a corrupt payload
                self.precompiled_buckets = {}  # must not kill the boot
                reason = f"deserialization failed ({exc!r})"
        if reason is not None:
            self.aot_fallback_reason = reason
            log.warning("%s: AOT executables ignored — %s; serving "
                        "falls back to JIT (buckets compile on warmup)",
                        self.name, reason)

    def _forward(self, params, x):
        for unit, p in zip(self._units, params):
            x = unit.xla_apply(p, x, rng=None, train=False)
        return x

    def __call__(self, x) -> np.ndarray:
        x = jnp.asarray(x)
        # AOT executables are pinned to (bucket,)+input_shape float32 —
        # anything else (a 1-D direct call whose LENGTH happens to equal
        # a bucket included) takes the general jit path as before
        if (x.ndim == len(self.input_shape) + 1
                and x.dtype == jnp.float32):
            fn = self.precompiled_buckets.get(x.shape[0])
            if fn is not None:
                return np.asarray(fn(self._params, x))
        return np.asarray(self._fn(self._params, x))


# -- CLI: python -m znicz_tpu aot <pkg.npz> ----------------------------------

def aot_main(argv) -> int:
    """Precompile a forward package's serving executables on this host
    (the deploy-time half of the zero-JIT boot: run this once per
    device class, serve everywhere that fingerprint matches)."""
    import argparse
    import sys
    import time

    p = argparse.ArgumentParser(
        prog="znicz_tpu aot",
        description="embed ahead-of-time serving executables (one per "
                    "engine bucket) into a forward package")
    p.add_argument("package", help="path to a utils/export.py .npz package")
    p.add_argument("--max-batch", type=int, default=64,
                   help="largest serving bucket to precompile (must "
                        "match the serve CLI's --max-batch)")
    p.add_argument("-o", "--output", default=None,
                   help="write the augmented package here instead of "
                        "updating in place")
    args = p.parse_args(argv)
    t0 = time.perf_counter()
    try:
        meta = attach_aot(args.package, max_batch=args.max_batch,
                          out=args.output)
    except (KeyError, OSError, ValueError, RuntimeError) as exc:
        print(f"aot: cannot precompile {args.package!r}: {exc}",
              file=sys.stderr)
        return 2
    fp = meta["fingerprint"]
    print(json.dumps({
        "package": args.output or args.package,
        "buckets": meta["buckets"],
        "platform": fp["platform"], "device_kind": fp["device_kind"],
        "jax": fp["jax"],
        "seconds": round(time.perf_counter() - t0, 2)}))
    return 0


# -- forge: local model-zoo packaging (reference: veles/forge) --------------
# Thin compatibility wrappers over the canonical registry implementation
# (znicz_tpu.utils.forge.ForgeRegistry: manifest + sha256 integrity +
# semantic version ordering).

def forge_publish(package_path: str, repo_dir: str, name: str,
                  version: str = "1.0", metrics: dict | None = None) -> str:
    """Publish a forward package (reference: veles forge upload)."""
    from znicz_tpu.utils.forge import ForgeRegistry

    reg = ForgeRegistry(repo_dir)
    entry = reg.upload(package_path, name, version, metadata=metrics or {})
    return os.path.join(repo_dir, entry["file"])


def forge_fetch(repo_dir: str, name: str,
                version: str | None = None) -> ExportedForward:
    """Fetch + load a published model (reference: veles forge fetch) —
    read in place from the registry (checksum-verified), no copy."""
    from znicz_tpu.utils.forge import ForgeRegistry

    return ExportedForward(ForgeRegistry(repo_dir).fetch(name, version))


def forge_list(repo_dir: str) -> dict:
    from znicz_tpu.utils.forge import ForgeRegistry

    return ForgeRegistry(repo_dir).list_packages()
